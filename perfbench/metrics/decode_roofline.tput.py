"""Least time of the traced decode micro-steps (weights, live KV read,
KV written; the larger of FLOPs and bytes over the chip's peaks) over
the decode program's device time."""
from harness import readings


def read(ctx):
    return readings.decode_roofline(ctx)
