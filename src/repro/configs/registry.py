"""Architecture registry: ``get_config(arch_id, variant)``.

Variants:
  * ``full``  — the exact assigned configuration (dry-run / roofline only;
                never materialized on CPU).
  * ``smoke`` — reduced same-family variant (<=4 layers, d_model<=512,
                <=4 experts) for CPU tests.
  * ``long``  — full config with the long-context attention policy applied
                (sliding window 8192 for softmax-attention archs; identity
                for SSM/chunked archs). Used by the ``long_500k`` shape.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

ARCH_IDS = [
    "deepseek-v3-671b",
    "llama-3.2-vision-90b",
    "seamless-m4t-large-v2",
    "zamba2-7b",
    "llama4-maverick-400b-a17b",
    "minicpm-2b",
    "rwkv6-1.6b",
    "stablelm-12b",
    "internlm2-20b",
    "llama3.2-1b",
    "deepseek-v2-lite",
]

_MODULES = {a: "repro.configs." + a.replace("-", "_").replace(".", "_")
            for a in ARCH_IDS}
_EXTRA = {
    "llama-paper-1b": "repro.configs.llama_paper",
    "llama-paper-3b": "repro.configs.llama_paper",
    "llama-paper-7b": "repro.configs.llama_paper",
}

VARIANTS = ("full", "smoke", "long")


def get_config(arch: str, variant: str = "full"):
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; use one of {VARIANTS}")
    mod_name = _MODULES.get(arch) or _EXTRA.get(arch)
    if mod_name is None:
        raise KeyError(f"unknown arch {arch!r}; known: {ARCH_IDS + sorted(_EXTRA)}")
    mod = importlib.import_module(mod_name)
    return mod.make(variant=variant, arch=arch)


def all_configs(variant: str = "full") -> Dict[str, object]:
    return {a: get_config(a, variant) for a in ARCH_IDS}
