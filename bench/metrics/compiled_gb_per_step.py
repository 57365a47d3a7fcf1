"""Bytes accessed by one step, as the compiler counts them statically
(``compiled.cost_analysis()["bytes accessed"]`` of the step program), in
GB (1e9)."""


def read(rec):
    return rec["compiled_bytes_per_step"] / 1e9
