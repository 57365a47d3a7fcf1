"""Device own milliseconds per step of the step's ``optimizer`` phase
(``bench/scopes.py``): gradient clipping and AdamW; the mean over the
devices in the traced window.  Nothing where the step has no such phase."""


def read(rec):
    return rec["scopes"] and rec["scopes"]["phases_ms"]["optimizer"] or None
