"""Peak device memory of the run: ``memory_stats()["peak_bytes_in_use"]``
after the window, the highest over the cell's devices, in GiB."""


def read(rec):
    return rec["memory_peak_bytes"] / 2**30
