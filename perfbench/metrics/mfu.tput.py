"""Model FLOPs of the traced prefill chunks and decode micro-steps over
their programs' device time times the chip's peak."""
from harness import readings


def read(ctx):
    return readings.mfu(ctx)
