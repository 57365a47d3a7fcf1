"""Device milliseconds per step in the collectives whose device groups span
the ``model`` mesh axis: the union of their intervals (an asynchronous one
from its start to its done; one over both axes counts in each), averaged
over the devices (``bench/scopes.py``).  Nothing where no collective spans
that axis."""


def read(rec):
    return rec["scopes"] and rec["scopes"]["axes_ms"].get("model") or None
