"""Device milliseconds per step in collective operations (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all; an
asynchronous one from its start to its done), averaged over the devices.
Nothing where the step has no collective."""


def read(rec):
    tr = rec["trace"]
    if tr is None or tr["collective_s"] == 0:
        return None
    return tr["collective_s"] / rec["steps"] * 1e3
