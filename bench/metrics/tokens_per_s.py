"""Tokens trained per second: every token of every step of the window, on
all chips, over the window's wall time (host clock, ending after
``block_until_ready``)."""


def read(rec):
    return rec["steps"] * rec["tokens_per_step"] / rec["window_s"]
