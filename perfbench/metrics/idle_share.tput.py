"""Share of the traced window in which no operation ran on the device."""
from harness import readings


def read(ctx):
    return readings.idle_share(ctx)
