"""Host milliseconds per step inside ``next()`` of the program's
``Prefetcher``, from the harness's ``bench.next_batch`` spans in the
traced window."""


def read(rec):
    tr = rec["trace"]
    if tr is None or "bench.next_batch" not in tr["host_s"]:
        return None
    return tr["host_s"]["bench.next_batch"] / rec["steps"] * 1e3
