"""Operations and bytes the algorithm needs, from a configuration's shapes.

These are the benchmark's own functions of the configuration file, not the
program's cost model or the compiler's HLO counts, so no program change
moves them. Each is its architecture module's function of the same name
(``architectures/<architecture>.py``; contract there), counted for the
chip's own share of the deployment. Only ``least_time`` is the same for
every architecture.
"""
from __future__ import annotations

from typing import Dict

from harness.model import architecture


def _by_architecture(name: str):
    def count(cfg: Dict, *args):
        return getattr(architecture(cfg), name)(cfg, *args)
    count.__name__ = count.__qualname__ = name
    count.__doc__ = f"The configuration's architecture module's ``{name}``."
    return count


parameters = _by_architecture("parameters")
kv_bytes_per_token = _by_architecture("kv_bytes_per_token")
decode_step = _by_architecture("decode_step")
prefill_chunk = _by_architecture("prefill_chunk")
decode_attention = _by_architecture("decode_attention")
memory = _by_architecture("memory")


def least_time(flops: float, nbytes: float, peak: Dict[str, float]) -> float:
    return max(flops / peak["flops_bf16"], nbytes / peak["hbm_bytes_per_s"])
