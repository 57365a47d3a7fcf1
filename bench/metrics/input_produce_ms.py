"""Host milliseconds per step in the program's own ``data.produce`` spans
(its ``Prefetcher`` worker making a batch and sending it to the devices)
inside the traced window.  Nothing where the program writes no such
span."""


def read(rec):
    return rec["scopes"] and rec["scopes"]["data_ms"].get("data.produce") or None
