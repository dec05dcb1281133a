"""Device self time of the decode program's ops under the model scope
``attn_decode`` (KV write, cache read, scores, softmax, value sum) per
traced decode micro-step."""
from harness import program_readings


def read(ctx):
    return program_readings.attn_decode_ms(ctx)
