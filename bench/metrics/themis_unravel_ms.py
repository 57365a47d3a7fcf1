"""Device own milliseconds per step of the step's ``themis_unravel`` phase
(``bench/scopes.py``): the parameter leaves from slices of the gathered
rows; the mean over the devices in the traced window.  Nothing where the
step has no such phase."""


def read(rec):
    return rec["scopes"] and rec["scopes"]["phases_ms"]["themis_unravel"] or None
