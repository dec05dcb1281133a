"""DeepSeek-V2's pieces in the program: YaRN rope and its softmax scale
against the published formulas, the dropless layer over held experts (its
shares add up to the uncut layer, its counts), latent pages refused by the
Pallas path, a two-shard fleet over latent pages token for token with
the single engine, and the stacked pool's writes and reads (a layer of
the scanned pool, latent and GQA alike) against the per-layer form.

The whole model against its float32 reference (chunked prefill, then paged
decode, and the departures the comparison must catch) is in
``perfbench/tests/test_perfbench_deepseek_v2.py``, beside the reference.
"""
import dataclasses
import math
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_latent_config, tiny_model
from repro.configs import get_config
from repro.models import Model
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as MOE
from repro.serving import EngineConfig, Request, ServingEngine

ROOT = Path(__file__).resolve().parents[1]


# ------------------------------------------------------------------ YaRN


def _published_inv_freq(dim, base, factor, orig, beta_fast, beta_slow):
    """DeepseekV2YarnRotaryEmbedding's inv_freq, in float64."""
    def corr(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (low, high), inter * (1 - mask) + extra * mask


def test_yarn_frequencies_and_softmax_scale_match_the_published_formulas():
    cfg = get_config("deepseek-v2-lite")
    y = cfg.rope_scaling
    (low, high), want = _published_inv_freq(64, 1e4, 40, 4096, 32, 1)
    assert (low, high) == (10, 23)
    assert L.yarn_correction_range(64, 1e4, y) == (10, 23)
    rot, inv = L.rope_frequencies(64, 1.0, 1e4, y)
    assert rot == 64
    np.testing.assert_allclose(np.asarray(inv), want, rtol=2e-7)
    assert A.mla_softmax_scale(cfg) == pytest.approx(0.1147214, abs=5e-8)
    want_scale = 192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2
    assert A.mla_softmax_scale(cfg) == pytest.approx(want_scale, rel=1e-12)
    # without scaling, rope and the MLA scale are what they were
    _, plain = L.rope_frequencies(64, 1.0, 1e4)
    np.testing.assert_array_equal(
        np.asarray(plain),
        1.0 / (1e4 ** (jnp.arange(0, 64, 2, dtype=jnp.float32) / 64)))
    v3 = get_config("deepseek-v3-671b")
    assert A.mla_softmax_scale(v3) == 1.0 / math.sqrt(192)


# ------------------------------------------------------- dropless experts


def _moe_cfg(**kw):
    cfg = tiny_latent_config()
    return dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, **kw))


def _layer_params(key, cfg):
    p = MOE.moe_init(key, cfg)
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), p)


def _plain_layer(p, cfg, x):
    """The published layer over every expert, in float64: softmax over all
    logits, greedy top-k, gates unnormalised, shared experts added."""
    m = cfg.moe
    x = np.asarray(x, np.float64).reshape(-1, cfg.d_model)
    f64 = {k: np.asarray(v, np.float64) for k, v in p.items()
           if k != "shared"}
    logits = x @ f64["router"]
    prob = np.exp(logits - logits.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    top = np.argsort(-prob, axis=-1)[:, :m.top_k]

    def swiglu(h, g, u, d):
        a = h @ g
        return (a / (1 + np.exp(-a)) * (h @ u)) @ d

    y = np.zeros_like(x)
    for t in range(x.shape[0]):
        for e in top[t]:
            y[t] += prob[t, e] * swiglu(
                x[t], f64["experts_gate"][e], f64["experts_up"][e],
                f64["experts_down"][e])
    sh = {k: np.asarray(v, np.float64) for k, v in p["shared"].items()}
    return y + swiglu(x, sh["w_gate"], sh["w_up"], sh["w_down"])


def test_expert_shares_add_up_to_the_uncut_layer():
    """Two chips of an EP2 deployment each hold 8 of the 16 experts: each
    runs the dropless layer over its own (the router's columns put in its
    order, so its experts are 0-7); the shares' routed parts, with the
    shared experts counted once, are the uncut layer — and the uncut
    layer is the published one."""
    whole = _moe_cfg(n_held_experts=0)
    p = _layer_params(jax.random.PRNGKey(3), whole)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 7, whole.d_model))
    uncut, _ = MOE.moe_ffn_held(p, whole, x)
    np.testing.assert_allclose(np.asarray(uncut).reshape(-1, whole.d_model),
                               _plain_layer(p, whole, x), rtol=2e-4,
                               atol=2e-5)
    half = _moe_cfg(n_held_experts=8)
    no_shared = dict(p, shared=jax.tree_util.tree_map(jnp.zeros_like,
                                                      p["shared"]))
    total = np.zeros(uncut.shape)
    for share in range(2):
        order = np.roll(np.arange(16), -8 * share)
        held = order[:8]
        ps = {"router": p["router"][:, order],
              "experts_gate": p["experts_gate"][held],
              "experts_up": p["experts_up"][held],
              "experts_down": p["experts_down"][held],
              "shared": no_shared["shared"]}
        y, _ = MOE.moe_ffn_held(ps, half, x)
        total += np.asarray(y)
    shared = L.swiglu(p["shared"], x.reshape(-1, whole.d_model))
    total += np.asarray(shared).reshape(total.shape)
    np.testing.assert_allclose(total, np.asarray(uncut), rtol=1e-5,
                               atol=1e-6)


def test_dropless_layer_counts_its_pairs_and_experts():
    cfg = _moe_cfg()
    p = _layer_params(jax.random.PRNGKey(5), cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 5, cfg.d_model))
    _, aux = MOE.moe_ffn_held(p, cfg, x)
    logits = np.asarray(x).reshape(-1, cfg.d_model) @ np.asarray(p["router"])
    top = np.argsort(-logits, axis=-1)[:, :cfg.moe.top_k]
    held = top[top < 8]
    assert int(aux["moe_pairs"]) == held.size
    assert int(aux["moe_experts_hit"]) == len(set(held.tolist()))


def test_rows_outside_every_group_do_not_reach_the_output(monkeypatch):
    """The grouped product promises nothing for the rows past the held
    pairs: filled with NaN there, the layer's output is unchanged."""
    cfg = _moe_cfg()
    p = _layer_params(jax.random.PRNGKey(7), cfg)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 6, cfg.d_model))
    want, _ = MOE.moe_ffn_held(p, cfg, x)
    ragged_dot = jax.lax.ragged_dot

    def nan_past_groups(lhs, rhs, group_sizes, **kw):
        out = ragged_dot(lhs, rhs, group_sizes, **kw)
        rows = jnp.arange(out.shape[0])[:, None]
        return jnp.where(rows < group_sizes.sum(), out, jnp.nan)

    monkeypatch.setattr(jax.lax, "ragged_dot", nan_past_groups)
    got, _ = MOE.moe_ffn_held(p, cfg, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_engine_reports_the_expert_counters():
    """The fused decode scan and the chunk program hand the counts back
    with the tokens; ``stats()`` carries them, a dense model has none."""
    _, m, params = tiny_model("latent", "tiny-counted")
    eng = ServingEngine(m, params, EngineConfig(
        max_batch=2, max_len=64, sync_every=4, paged=True, page_size=8,
        prefill_chunk=16, prefix_sharing=True))
    rng = np.random.default_rng(2)
    for i, n in enumerate((20, 9)):
        eng.submit(Request(rid=i, prompt=rng.integers(0, 256, n).tolist(),
                           max_new_tokens=6))
    eng.run()
    st = eng.stats()
    layers, k = 2, m.cfg.moe.top_k
    # every micro-step routes both slots' rows through both expert layers
    assert 0 < st["moe_decode_pairs"] <= eng._steps * 2 * layers * k
    assert 0 < st["moe_decode_experts_hit"] <= st["moe_decode_pairs"]
    assert 0 < st["moe_prefill_pairs"] <= 2 * 16 * layers * k
    assert not eng._pending_counts
    _, dm, dparams = tiny_model("dense", "tiny-uncounted")
    dense = ServingEngine(dm, dparams, EngineConfig(
        max_batch=2, max_len=64, paged=True, page_size=8, prefill_chunk=16))
    assert "moe_decode_pairs" not in dense.stats()


def test_pallas_with_latent_pages_raises():
    cfg = dataclasses.replace(tiny_latent_config(),
                              attn_impl="pallas_interpret")
    m = Model(cfg)
    eng = ServingEngine(m, m.init(jax.random.PRNGKey(0)), EngineConfig(
        max_batch=2, max_len=64, paged=True, page_size=8, prefill_chunk=16))
    eng.submit(Request(rid=0, prompt=[1, 2, 3, 4, 5], max_new_tokens=3))
    with pytest.raises(ValueError, match="latent"):
        eng.run()


def test_latent_pages_match_the_contiguous_cache():
    """Paged decode, chunked prefill and prefix sharing over latent pages
    serve the tokens the contiguous MLA cache serves."""
    _, m, params = tiny_model("latent", "tiny-latent-parity")
    rng = np.random.default_rng(8)
    shared = rng.integers(0, 256, 16).tolist()
    prompts = [shared + rng.integers(0, 256, n).tolist() for n in (4, 19, 9)]

    def run(**kw):
        eng = ServingEngine(m, params, EngineConfig(max_batch=2, max_len=64,
                                                    sync_every=4, **kw))
        # a long-lived donor: the followers admitted later adopt its prefix
        for i, (p, n) in enumerate(zip(prompts, (30, 6, 6))):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=n))
        return {r.rid: r.tokens for r in eng.run()}, eng

    want, _ = run()
    paged, _ = run(paged=True, page_size=8, num_pages=16)
    chunked, eng = run(paged=True, page_size=8, prefill_chunk=8,
                       prefix_sharing=True, num_pages=16)
    assert paged == want and chunked == want
    assert eng.prefix_hit_tokens > 0


# ------------------------------------------------------- the stacked pool

# page leaves (name, heads, width) of an attention and of a latent group
STACK_GROUPS = {"gqa": (("k_pages", 2, 8), ("v_pages", 2, 8)),
                "latent": (("ckv_pages", 1, 16), ("krope_pages", 1, 8))}


def _stacked_case(group):
    """A 3-layer stacked pool, its layer-1 slice as a per-layer group, and
    a block table in which slot 1 maps no page (a coasting slot)."""
    L_, B, M, ps, P = 3, 3, 4, 4, 9
    rng = np.random.default_rng(31)
    tbl = np.full((B, M), -1, np.int32)
    pages = rng.permutation(P)
    tbl[0, :3], tbl[2, :4] = pages[:3], pages[3:7]
    length = np.asarray([9, 0, 14], np.int32)
    pos = np.arange(M * ps)[None].repeat(B, 0)
    pos = np.where(pos < length[:, None], pos, -1).astype(np.int32)
    leaves = {n: jnp.asarray(rng.normal(size=(L_, h, P + 1, ps, w)),
                             jnp.float32) for n, h, w in STACK_GROUPS[group]}
    cursors = {"pos_ids": jnp.asarray(pos), "length": jnp.asarray(length)}
    stacked = {**leaves, **cursors, A.PAGE_LAYER: jnp.int32(1)}
    layer = {**{n: x[1] for n, x in leaves.items()}, **cursors}
    return stacked, layer, jnp.asarray(tbl), rng


@pytest.mark.parametrize("op", ["decode_write", "chunk_write", "read"])
@pytest.mark.parametrize("group", sorted(STACK_GROUPS))
def test_stacked_pool_matches_the_layer_form(group, op):
    """Writing rows into a layer of the stacked pool changes exactly what
    the same write changes in that layer's own leaves, and nothing of the
    other layers; reading the layer where it lies (the pool scored in
    place, a slot's gathered view, the latent chunk loop) gives the
    per-layer form's numbers exactly."""
    stacked, layer, tbl, rng = _stacked_case(group)
    names = [n for n, _, _ in STACK_GROUPS[group]]
    B = tbl.shape[0]
    if op != "read":
        S = 1 if op == "decode_write" else 5
        new = {n: jnp.asarray(rng.normal(size=(B, S) + layer[n].shape[::3]),
                              jnp.float32) for n in names}
        if op == "decode_write":
            got = A._paged_decode_write(stacked, tbl, new)
            want = A._paged_decode_write(layer, tbl, new)
        else:
            t0 = stacked["length"]
            positions = jnp.where(jnp.arange(S)[None] < jnp.asarray(
                [[5], [0], [2]]), t0[:, None] + jnp.arange(S), -1)
            got = A._paged_chunk_write(stacked, tbl, new, positions)
            want = A._paged_chunk_write(layer, tbl, new, positions)
        for n in names:
            np.testing.assert_array_equal(got[n][1], want[n])
            np.testing.assert_array_equal(np.delete(got[n], 1, 0),
                                          np.delete(stacked[n], 1, 0))
        for n in ("pos_ids", "length"):
            np.testing.assert_array_equal(got[n], want[n])
        return
    positions = stacked["length"][:, None] - 1
    for n in names:
        np.testing.assert_array_equal(
            A.gather_pages_hb(stacked[n], tbl, stacked[A.PAGE_LAYER]),
            A.gather_pages_hb(layer[n], tbl))
    if group == "gqa":
        q = jnp.asarray(rng.normal(size=(B, 1, 4, 8)), jnp.float32)
        reads = [lambda c: A._pool_decode_attention(
            (q,), c, tbl, positions, None, None, 2)]
    else:
        H = 4
        ql = jnp.asarray(rng.normal(size=(B, 1, H, 16)), jnp.float32)
        qr = jnp.asarray(rng.normal(size=(B, 1, H, 8)), jnp.float32)
        qn = jnp.asarray(rng.normal(size=(B, 3, H, 6)), jnp.float32)
        qc = jnp.asarray(rng.normal(size=(B, 3, H, 8)), jnp.float32)
        w_uk = jnp.asarray(rng.normal(size=(16, H, 6)), jnp.float32)
        w_uv = jnp.asarray(rng.normal(size=(16, H, 5)), jnp.float32)
        chunk_pos = positions + jnp.arange(3) - 2
        reads = [lambda c: A._pool_decode_attention(
                     (ql, qr), c, tbl, positions, None, None, H,
                     keys=A.LATENT_PAGES, values="ckv_pages", scale=0.3),
                 lambda c: A._mla_chunk_attention(
                     qn, qc, c, tbl, chunk_pos, w_uk, w_uv, 0.3, None)]
    for read in reads:
        np.testing.assert_array_equal(read(stacked), read(layer))


# --------------------------------------------------------------- fleet

FLEET = """
import sys
sys.path.insert(0, {tests!r})
import jax, numpy as np
assert jax.device_count() == 2, jax.devices()
from conftest import tiny_model
from repro.serving import (EngineConfig, Request, ServingEngine,
                           ShardedServingEngine)
_, m, params = tiny_model("latent", "tiny-latent-fleet")
rng = np.random.default_rng(5)
shared = rng.integers(0, 256, 16).tolist()
reqs = [dict(rid=i, prompt=shared + rng.integers(0, 256, int(n)).tolist(),
             max_new_tokens=7) for i, n in enumerate((3, 11, 6, 17, 8))]
args = dict(max_len=64, sync_every=4, paged=True, page_size=8,
            prefill_chunk=8, prefix_sharing=True)
single = ServingEngine(m, params, EngineConfig(max_batch=4, **args))
fleet = ShardedServingEngine(m, params, EngineConfig(max_batch=2, shards=2,
                                                     **args))
out = []
for eng in (single, fleet):
    for r in reqs:
        eng.submit(Request(**r))
    out.append({{r.rid: r.tokens for r in eng.run()}})
assert out[0] == out[1], out
assert all(len(t) == 7 for t in out[1].values())
print("FLEET_OK", fleet.stats()["prefix_hit_tokens"])
"""


def test_two_shard_fleet_over_latent_pages_matches_the_single_engine():
    """A two-shard ``ShardedServingEngine`` on two host devices (a fresh
    process: the device count is fixed at JAX's start) serves the latent
    model token for token as the single engine does."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(FLEET.format(
            tests=str(ROOT / "tests")))],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "FLEET_OK" in proc.stdout
