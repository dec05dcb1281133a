"""Prompt tokens adopted from the prefix index over the prompt tokens of
the requests admitted in the window."""
from harness import readings


def read(ctx):
    return readings.prefix_hit_share(ctx)
