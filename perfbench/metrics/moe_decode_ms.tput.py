"""Device self time of the decode program's ops under the dropless expert
layer's scopes ``moe_route`` (router, top-k, dispatch, combine) and
``moe_experts`` (the held experts' products) per traced decode
micro-step."""
from harness import moe_readings


def read(ctx):
    return moe_readings.moe_decode_ms(ctx)
