"""A cell small enough for the CPU: the harness's whole run, at a size a
test can hold. Float32, because the comparison's limit below is set for a
program and a reference that compute in the same precision."""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
for p in (str(ROOT / "src"), str(BENCH_DIR)):
    if p not in sys.path:
        sys.path.insert(0, p)

CONFIG = {
    "name": "tiny", "architecture": "dense_decoder", "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 3, "vocab_size": 4000,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "tie_word_embeddings": True,
    "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 64,
    "torch_dtype": "float32",
    "assumed": {"pad_heads_to_multiple": 1, "vocab_pad_multiple": 8,
                "embedding_std": 0.02},
    "engine": {"paged": True, "page_size": 16, "prefill_chunk": 32,
               "prefix_sharing": True, "sync_every": 2, "temperature": 0.0,
               "profile": "tpu_v5e", "max_len": 192, "max_batch": 2,
               "num_pages": 24},
    # float32 program against the float32 reference: greedy tokens agree
    # and the gap reads 0 to rounding
    "check": {"widest_logit_gap": 1e-3},
}

MIX = {
    "loop": "closed", "workers_per_slot": 2, "shared_prefix_tokens": 32,
    "document": {"kind": "uniform", "min": 20, "max": 60},
    "turns": {"kind": "uniform", "min": 1, "max": 2},
    "turn_tokens": {"kind": "uniform", "min": 4, "max": 12},
    "output_tokens": {"kind": "lognormal", "median": 12, "sigma": 0.5,
                      "min": 4, "max": 24},
    "strata": 8, "warmup_s": 0.3, "drain_s": 0,
}


def load_run():
    """perfbench/run.py as a module."""
    spec = importlib.util.spec_from_file_location("perfbench_run",
                                                  BENCH_DIR / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell():
    from harness import spec
    bench = spec.load_benchmark(ROOT)
    return spec.Cell(name="tiny", chips=1, config=CONFIG, traffic=MIX,
                     end_to_end=bench["end_to_end"], per_layer=[])


def run_tiny(seed: int, seconds: float = 1.0) -> dict:
    import time
    from harness.device import CompileLog
    run = load_run()
    return run.run_cell(cell(), seed, seconds, False,
                        {"platform": "cpu", "kind": "cpu", "count": 1},
                        CompileLog(), time.perf_counter())
