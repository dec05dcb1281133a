"""Device time of the fused decode program per micro-step it ran."""
from harness import readings


def read(ctx):
    return readings.decode_step_ms(ctx)
