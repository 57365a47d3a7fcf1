"""Device own milliseconds per step of the step's ``forward`` phase
(``bench/scopes.py``): the forward pass and the loss, ``jvp(forward)``;
the mean over the devices in the traced window.  Nothing where the step
has no such phase."""


def read(rec):
    return rec["scopes"] and rec["scopes"]["phases_ms"]["forward"] or None
