"""Least time of decode attention's own work (scores and value sum over
p + 1 keys, the live KV read and one row written per slot; the larger of
FLOPs and bytes over the chip's peaks) over the device self time under
``attn_decode``."""
from harness import program_readings


def read(ctx):
    return program_readings.attn_decode_roofline(ctx)
