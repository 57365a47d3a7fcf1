"""Device own milliseconds per step of the step's ``themis_flatten`` phase
(``bench/scopes.py``): the f32 chunk rows built from the gradient leaves;
the mean over the devices in the traced window.  Nothing where the step
has no such phase."""


def read(rec):
    return rec["scopes"] and rec["scopes"]["phases_ms"]["themis_flatten"] or None
