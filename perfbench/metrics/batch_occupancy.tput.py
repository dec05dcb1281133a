"""Decode tokens per decode micro-step slot over the window, from the
engine's counters: delta decode_tokens / (delta steps * max_batch *
shards)."""
from harness import readings


def read(ctx):
    return readings.batch_occupancy(ctx)
