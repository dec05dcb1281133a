"""A cell on more than one chip: the harness serves it through the
program's ``ShardedServingEngine``, one shard per chip, with the weights
replicated by one call; the reference reads one replica. On the CPU the
chips are host devices, which have to be asked for before JAX starts, so
each run goes in a process of its own."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import perfbench_tiny as tiny

SCRIPT = """
import json, sys
import jax
import jax.numpy as jnp
import perfbench_tiny as tiny
assert jax.device_count() == 2, jax.devices()
fault = sys.argv[2]
if fault == "token_altered":        # the second-best token where sampled
    from repro.serving import sampling
    real = sampling.sample
    def second_best(logits, key, temperature):
        top = real(logits, key, temperature)
        return real(logits.at[jnp.arange(logits.shape[0]), top]
                    .set(-jnp.inf), key, temperature)
    sampling.sample = second_best
elif fault == "decode_state_unchanged":   # decode writes no KV
    from repro.models import attention
    attention.paged_decode_write = lambda cache, tbl, k1, v1: cache
out = tiny.run_fleet(seed=2**32 + 41, chips=2, root=sys.argv[1])
rep = out.pop("_report")
print(json.dumps({"result": out, "report": rep}, default=str))
"""


def _fleet_run(tmp_path, fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join(
                   [str(tiny.BENCH_DIR / "tests"), str(tiny.BENCH_DIR),
                    str(tiny.ROOT / "src")]))
    proc = subprocess.run([sys.executable, "-c", SCRIPT, str(tmp_path), fault],
                          cwd=tmp_path, env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    return got["result"], got["report"]


def test_a_two_shard_fleet_serves_correctly_and_reports(tmp_path):
    out, rep = _fleet_run(tmp_path, "none")
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["count"] == 2
    assert rep["compiles_in_window"] == 0
    # later turns resend the conversation, so their prompts outgrow any
    # single turn of the mix
    assert max(n for n, _ in rep["sampled_requests"]) > 32 + 12
    metrics = out["metrics"]
    assert 0 < metrics["batch_occupancy.tput"]["value"] <= 100
    assert 0 < metrics["prefix_hit_share.tput"]["value"] <= 100
    # the fleet's quanta carry no page counts, and the CPU has no device
    # trace: those readers find nothing and the metrics stay out
    assert "kv_mapped_share.tput" not in metrics
    assert "host_stall_ms.tput" not in metrics


@pytest.mark.parametrize("fault", ["token_altered", "decode_state_unchanged"])
def test_a_fleet_with_its_timed_path_broken_is_not_correct(tmp_path, fault):
    out, _ = _fleet_run(tmp_path, fault)
    gap = out["checks"]["widest_logit_gap"]
    assert not out["correct"]
    assert gap["value"] > gap["limit"]
