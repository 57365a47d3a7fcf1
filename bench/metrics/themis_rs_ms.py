"""Device own milliseconds per step of the step's ``themis_rs`` phase
(``bench/scopes.py``): the chunk reduce-scatter hops and the stack of
their shards; the mean over the devices in the traced window.  Nothing
where the step has no such phase."""


def read(rec):
    return rec["scopes"] and rec["scopes"]["phases_ms"]["themis_rs"] or None
