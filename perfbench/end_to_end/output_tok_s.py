"""Output tokens that reached clients inside the window, over the window."""
from harness import readings


def read(ctx):
    return readings.output_tok_s(ctx)
