"""Device time of the chunked-prefill program per launch, from the trace."""
from harness import readings


def read(ctx):
    return readings.prefill_chunk_ms(ctx)
