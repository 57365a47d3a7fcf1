"""Model FLOPs utilization of the whole step: the model FLOPs of a step
(``bench/flops.py``) times the window's steps, over the window's wall
time, over the cell's chips times the chip's bf16 peak (``bench/peaks.py``),
in percent."""


def read(rec):
    rate = rec["model_flops_per_step"] * rec["steps"] / rec["window_s"]
    return 100 * rate / (rec["chips"] * rec["peak"]["bf16_flops_per_s"])
