"""Device self time of the chunked-prefill program's ops under the model
scope ``attn_prefill`` per launch."""
from harness import program_readings


def read(ctx):
    return program_readings.attn_prefill_ms(ctx)
