"""Seconds from the start of the process to the first timed step: imports,
device start, model build, compilation or compile-cache read, state
initialised from the seed, and the first three steps."""


def read(rec):
    return rec["setup_s"]
