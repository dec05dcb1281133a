"""One serving engine at every shard count: a single device is the
one-shard case of the fleet.

``ServingEngine`` serves ``cfg.shards`` = S shards. At S = 1 it launches
the single-device programs on the unstacked state, so the served
programs are exactly those a one-chip deployment compiles; at S > 1 it
launches their shard_map forms over the mesh, and the fleet then counts
what the one device counts (page quanta, MoE pairs). The fleet runs here
in a fresh process on two host devices (the device count is fixed at
JAX's start), so tier-1 covers it without the four-device suites.
"""
import inspect
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from conftest import tiny_model
from repro.serving import EngineConfig, Request, ServingEngine
from repro.serving import engine as E
from repro.serving import sharded

ROOT = Path(__file__).resolve().parent.parent
PS = 8
CH = 8


@pytest.fixture(scope="module")
def parts():
    return tiny_model("dense", "tiny-one-engine")


def _serve(eng, reqs):
    for r in reqs:
        eng.submit(Request(**r))
    return {r.rid: r.tokens for r in eng.run()}


def _reqs(seed=3):
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, 256, 2 * PS).tolist()
    return [dict(rid=i, prompt=shared + rng.integers(0, 256, n).tolist(),
                 max_new_tokens=6) for i, n in enumerate((3, 9, 5, 12))]


def test_one_shard_launches_the_single_device_programs(parts):
    """At S = 1 the decode scan and the chunk program the engine runs are
    the single-device jit entries; their shard_map forms compile
    nothing."""
    _, m, params = parts
    one = (E._FUSED_STEPS, E._CHUNK_PREFILL)
    fleet = (E._FUSED_FLEET, E._CHUNK_FLEET, E._BEGIN_FLEET, E._ARM_FLEET,
             E._RELEASE_FLEET, E._MAP_PREFIX_FLEET)
    before = [f._cache_size() for f in one + fleet]
    eng = ServingEngine(m, params, EngineConfig(
        max_batch=4, max_len=64, sync_every=4, paged=True, page_size=PS,
        prefill_chunk=CH, prefix_sharing=True))
    out = _serve(eng, _reqs())
    assert all(len(t) == 6 for t in out.values())
    after = [f._cache_size() for f in one + fleet]
    assert all(a > b for a, b in zip(after[:2], before[:2]))
    assert after[2:] == before[2:]
    assert eng.mesh is None and eng.stats()["shards"] == 1


def test_fleet_name_is_the_engine_at_one_shard(parts):
    """``ShardedServingEngine`` is the same class: built with one shard
    and no mesh it serves what ``ServingEngine`` serves."""
    _, m, params = parts
    assert sharded.ShardedServingEngine is ServingEngine
    cfg = dict(max_batch=4, max_len=64, sync_every=4, paged=True,
               page_size=PS, prefill_chunk=CH, prefix_sharing=True)
    want = _serve(ServingEngine(m, params, EngineConfig(**cfg)), _reqs(5))
    got = _serve(sharded.ShardedServingEngine(
        m, params, EngineConfig(shards=1, **cfg)), _reqs(5))
    assert got == want


def test_each_engine_method_is_defined_once():
    """The fleet operations are a mixin of the one engine class: no
    method name is defined in both."""
    def methods(cls):
        return {k for k, v in vars(cls).items()
                if inspect.isfunction(v) or isinstance(v, property)}
    assert ServingEngine.__mro__[1] is sharded.FleetOps
    assert not methods(ServingEngine) & methods(sharded.FleetOps)


FLEET = """
import sys
sys.path.insert(0, {tests!r})
import jax, numpy as np
assert jax.device_count() == 2, jax.devices()
from conftest import tiny_model
from repro.serving import EngineConfig, Request, ServingEngine

rng = np.random.default_rng(7)
shared = rng.integers(0, 256, 16).tolist()
low = [dict(rid=i, prompt=shared + rng.integers(0, 256, int(n)).tolist(),
            max_new_tokens=20) for i, n in enumerate((4, 9, 6, 11))]
high = [dict(rid=10 + i, prompt=shared + rng.integers(0, 256, 5).tolist(),
             max_new_tokens=5, priority=1) for i in range(2)]


def serve(m, params, shards, **kw):
    eng = ServingEngine(m, params, EngineConfig(
        max_batch=4 // shards, shards=shards, max_len=64, sync_every=4,
        paged=True, page_size=8, prefill_chunk=8, prefix_sharing=True,
        **kw))
    for r in low:
        eng.submit(Request(**r))
    for _ in range(6):
        eng.step()
    for r in high:
        eng.submit(Request(**r))
    return {{r.rid: r.tokens for r in eng.run()}}, eng.stats()


_, m, params = tiny_model("dense", "tiny-one-engine-fleet")
one, st1 = serve(m, params, 1, preemption=True)
two, st2 = serve(m, params, 2, preemption=True)
assert one == two, (one, two)
assert st1["shards"] == 1 and st2["shards"] == 2
assert st1["preemption_count"] > 0 and st2["preemption_count"] > 0
assert st2["prefix_hit_tokens"] > 0
assert 0 < st2["mapped_page_quanta"] <= st2["reserved_page_quanta"], st2

_, m, params = tiny_model("latent", "tiny-latent-one-engine")
one, _ = serve(m, params, 1)
two, st2 = serve(m, params, 2)
assert one == two, (one, two)
assert st2["moe_decode_pairs"] > 0 and st2["moe_prefill_pairs"] > 0, st2
print("ONE_ENGINE_OK")
"""


def test_two_shards_serve_the_one_shard_tokens_and_count_alike():
    """Two shards on two host devices serve token for token what one
    shard serves, with prefix sharing and priority preemption on, and
    their stats carry the page quanta and (for the latent MoE model) the
    MoE pair counts a single device reports."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(FLEET.format(
            tests=str(ROOT / "tests")))],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "ONE_ENGINE_OK" in proc.stdout
