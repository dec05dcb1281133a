"""chip_smoke.py on the CPU: its serving phase at the smoke width with the
kernels in interpret mode, and its refusal to run without a TPU.

The phase is the one the chip runs — ``AsyncServingServer`` over the
paged, chunked, prefix-sharing engine, the sharer arriving after its
donor's first token — scaled down to a 2-layer float32 model, so the
kernel path must match the jnp path to float32 precision.
"""
import dataclasses
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import llama_paper
from repro.models import Model

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod          # its dataclass resolves here
    spec.loader.exec_module(mod)
    return mod


def test_serving_phase_interpret_matches_ref(smoke):
    cfg = llama_paper.make("smoke", "llama-paper-1b")
    params = Model(cfg).init(jax.random.PRNGKey(0))
    engine_cfg = dataclasses.replace(smoke.ENGINE, page_size=8,
                                     prefill_chunk=32, max_batch=4,
                                     max_len=128, sync_every=2)
    reqs = smoke.make_requests(0, cfg.vocab, n=4, lens=(10, 90),
                               page_size=8, shared_pages=3, max_new_tokens=8)
    assert reqs[-1].prompt == reqs[0].prompt[:24]
    log = smoke.CompileLog()
    kernel, = smoke.serving_phase(cfg, "pallas_interpret", params, reqs, log,
                                  engine_cfg)
    ref, = smoke.serving_phase(cfg, "ref", params, reqs, log, engine_cfg)
    for run in (kernel, ref):
        assert set(run.reasons.values()) == {"length"}
        assert all(len(t) == 8 for t in run.tokens.values())
        assert run.stats["prefix_hit_tokens"] > 0       # the sharer adopted
        assert set(run.logits) == {r.rid for r in reqs}
    cmp = smoke.compare(kernel, ref)
    assert cmp["logit_rel_err"] < 1e-4
    assert cmp["first_token_agree"] == cmp["requests"] == 4
    assert cmp["identical_streams"] == 4
    assert cmp["common_prefix_tokens"] == cmp["tokens"] == 32


def test_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_dir(env_dir, tmp_path):
    """The entry points' cache: JAX_COMPILATION_CACHE_DIR when set (JAX
    reads it; the helper sets nothing), else .jax_cache/ at the repo
    root. Checked in a child process so this one's cache stays off."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    code = ("import jax; from repro.launch.compile_cache import "
            "enable_compile_cache as e; print(e()); "
            "print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    returned, configured = out.stdout.split()
    want = (str(tmp_path / env_dir) if env_dir is not None
            else str(ROOT / ".jax_cache"))
    assert returned == configured == want
