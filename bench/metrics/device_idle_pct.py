"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals, collectives from start to done
included) / window, averaged over the cell's devices, in percent."""


def read(rec):
    tr = rec["trace"]
    if tr is None:
        return None
    return 100 * (1 - tr["busy_s"] / tr["window_s"])
