"""step_mfu: analytic model FLOPs of the window's steps (recompute
excluded) over its wall time, as a share of the chip's bf16 peak."""


def read(r):
    return 100.0 * r.flops_per_step * r.steps / r.window_s / r.peak[
        "bf16_flops"]
