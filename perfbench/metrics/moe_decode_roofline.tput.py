"""Least time of the held experts' decode work over the device self time
under ``moe_route`` and ``moe_experts``: FLOPs of the token-expert pairs
the program counted (3 products of hidden x expert width a pair) and the
router's rows; bytes of each expert that received a pair and the
routers' weights (the architecture's ``moe_decode``)."""
from harness import moe_readings


def read(ctx):
    return moe_readings.moe_decode_roofline(ctx)
