"""Device own milliseconds per step of the step's ``backward`` phase
(``bench/scopes.py``): the backward pass, ``transpose(jvp(forward))``,
remat recomputation included; the mean over the devices in the traced
window.  Nothing where the step has no such phase."""


def read(rec):
    return rec["scopes"] and rec["scopes"]["phases_ms"]["backward"] or None
