"""The chip a run stands on: its identity, its peaks, its memory, and the
compile work JAX reports."""
from __future__ import annotations

import os
from pathlib import Path
from typing import Dict, Optional

# Published peaks per chip, keyed by jax's device_kind. Source: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def peaks(kind: str) -> Dict[str, float]:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {kind!r}; add it to "
                       "perfbench/harness/device.py from a published "
                       "source") from None


def check_devices(chips: int) -> Dict:
    """The chips this run measures on; raises NoChip when JAX finds no
    TPU or fewer than ``chips``."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (devices: {devs})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX has {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak_bytes() -> Optional[int]:
    import jax
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else int(stats["peak_bytes_in_use"])


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, every program cached however quick its compile."""
    import jax
    path = root / ".perfbench_cache" / "jax"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(path)


class CompileLog:
    """Compile work from JAX's monitoring events since creation: programs
    traced, and backend compiles (a load from the persistent cache counts
    as one)."""
    _TRACE = "/jax/core/compile/jaxpr_trace_duration"
    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.compiles = 0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == self._TRACE:
            self.traces += 1
        elif event == self._COMPILE:
            self.compiles += 1
