"""Device own milliseconds per step of the step's ``themis_ag`` phase
(``bench/scopes.py``): the rows of the updated master copy, cast, and the
chunk all-gather hops; the mean over the devices in the traced window.
Nothing where the step has no such phase."""


def read(rec):
    return rec["scopes"] and rec["scopes"]["phases_ms"]["themis_ag"] or None
