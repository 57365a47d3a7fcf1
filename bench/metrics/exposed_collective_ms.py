"""The part of ``collective_ms`` during which no other operation ran on
that device, per step, averaged over the devices.  Nothing where the step
has no collective."""


def read(rec):
    tr = rec["trace"]
    if tr is None or tr["collective_s"] == 0:
        return None
    return tr["exposed_collective_s"] / rec["steps"] * 1e3
