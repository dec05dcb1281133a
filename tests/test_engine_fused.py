"""Fused engine step: greedy parity with the seed per-token Python loop,
host-sync accounting, padded batched prefill, and the GQA-grouped decode
kernel's one-HBM-read-per-group contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.models import Model, ModelConfig
from repro.models.config import repeat_pattern
from repro.serving import EngineConfig, Request, ServingEngine


@pytest.fixture(scope="module")
def parts():
    cfg = ModelConfig(
        name="tiny-fused", family="dense", n_layers=2, d_model=64,
        n_heads=8, n_kv_heads=2, d_ff=128, vocab=256, dtype="float32",
        block_pattern=repeat_pattern(("dense",), 2), vocab_pad_multiple=8)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def seed_python_loop(cfg, m, params, prompt, max_new, max_len=64):
    """The seed engine's per-token hot path: per-request prefill, Python
    greedy sampling, one decode_step dispatch + host readback per token."""
    toks = jnp.asarray(prompt, jnp.int32)[None]
    last, caches = m.prefill(params, toks, max_len=max_len)
    out = [int(jnp.argmax(last[0, :cfg.vocab]))]
    for _ in range(max_new - 1):
        lg, caches = m.decode_step(
            params, caches, jnp.asarray([[out[-1]]], jnp.int32))
        out.append(int(jnp.argmax(lg[0, :cfg.vocab])))
    return out


def test_fused_step_matches_seed_loop_token_for_token(parts):
    """Mixed prompt lengths across buckets, continuous batching over more
    requests than slots — every response must equal the seed loop."""
    cfg, m, params = parts
    rng = np.random.default_rng(7)
    prompts = [list(rng.integers(0, 256, int(n)))
               for n in (3, 5, 8, 11, 16, 21, 4)]
    eng = ServingEngine(m, params, EngineConfig(
        max_batch=4, max_len=64, sync_every=8))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=9))
    resps = {r.rid: r for r in eng.run()}
    for i, p in enumerate(prompts):
        want = seed_python_loop(cfg, m, params, p, 9)
        assert resps[i].tokens == want, f"request {i} diverged"


def test_eos_terminates_on_device(parts):
    """EOS masking runs on device: the EOS token is emitted, then the slot
    stops — identical to the seed loop's semantics."""
    cfg, m, params = parts
    prompt = [9, 8, 7, 6, 5]
    full = seed_python_loop(cfg, m, params, prompt, 12)
    eos = full[4]                      # force a stop partway through
    eng = ServingEngine(m, params, EngineConfig(max_batch=2, max_len=64))
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=12, eos_id=eos))
    got = eng.run()[0].tokens
    cut = full.index(eos) + 1
    assert got == full[:cut]


def test_host_syncs_bounded_by_sync_every(parts):
    """At most 1 decode host sync per sync_every decode steps."""
    _, m, params = parts
    eng = ServingEngine(m, params, EngineConfig(
        max_batch=4, max_len=64, sync_every=8))
    for i in range(4):
        eng.submit(Request(rid=i, prompt=[1 + i, 2, 3], max_new_tokens=17))
    eng.run()
    st = eng.stats()
    assert st["steps"] == 16           # 17 tokens: 1 prefill + 16 decode
    assert st["decode_chunks"] <= -(-st["steps"] // 8)
    # same-shape prompts admitted together: one prefill batch, one sync
    assert st["prefill_batches"] == 1


def test_padded_prefill_batch_matches_unpadded(parts):
    """Bucketed right-padded prefill is exact: per-sequence last logits and
    caches match per-request unpadded prefill."""
    cfg, m, params = parts
    p0, p1 = [5, 6, 7], [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11]
    bucket = 16
    tokens = np.zeros((2, bucket), np.int32)
    mask = np.zeros((2, bucket), np.int32)
    for i, p in enumerate((p0, p1)):
        tokens[i, :len(p)] = p
        mask[i, :len(p)] = 1
    last_b, caches_b = m.prefill(params, jnp.asarray(tokens),
                                 {"mask": jnp.asarray(mask)}, max_len=32)
    for i, p in enumerate((p0, p1)):
        last_1, _ = m.prefill(params, jnp.asarray(p, jnp.int32)[None],
                              max_len=32)
        np.testing.assert_allclose(np.asarray(last_b[i]),
                                   np.asarray(last_1[0]),
                                   rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(caches_b["t"]),
                                  [len(p0), len(p1)])


def test_bucket_clamped_to_max_len_keeps_real_tokens(parts):
    """A pow2 bucket larger than the cache ring must not pad past max_len
    (pads would evict real tokens); prompts longer than max_len prefill at
    exact length. Both must stay token-for-token equal to the seed loop."""
    cfg, m, params = parts
    rng = np.random.default_rng(11)
    max_len = 24                           # non-power-of-two ring
    prompts = [list(rng.integers(0, 256, n)) for n in (18, 40, 5)]
    eng = ServingEngine(m, params, EngineConfig(max_batch=2, max_len=max_len))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=6))
    resps = {r.rid: r for r in eng.run()}
    for i, p in enumerate(prompts):
        want = seed_python_loop(cfg, m, params, p, 6, max_len=max_len)
        assert resps[i].tokens == want, f"request {i} diverged"


def test_max_new_tokens_one_emits_one(parts):
    """max_new_tokens=1: the prefill token is the whole budget — exactly
    one token, slot freed without entering the decode pool."""
    _, m, params = parts
    eng = ServingEngine(m, params, EngineConfig(max_batch=2, max_len=32))
    eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=1))
    eng.submit(Request(rid=1, prompt=[4, 5, 6], max_new_tokens=4))
    resps = {r.rid: r for r in eng.run()}
    assert len(resps[0].tokens) == 1 and resps[0].finished
    assert len(resps[1].tokens) == 4 and resps[1].finished


# ---------------------------------------------------------------- kernel


def test_decode_grid_is_grouped_by_kv_head():
    """The decode grid iterates KV heads, not query heads: each KV block is
    pulled from HBM exactly once per GQA group."""
    spec = ops.decode_grid_spec(B=2, Hq=8, Hkv=2, W=64, hd=16, hd_v=16,
                                block_k=32)
    assert spec["grid"] == (2, 2, 2)           # (B, Hkv, nk) — NOT (B, Hq, nk)
    assert spec["group"] == 4
    assert spec["q_block"] == (1, 1, 4, 16)    # whole group rides one program
    assert spec["k_block"] == (1, 1, 32, 16)   # one KV head per program
    assert spec["v_block"] == (1, 1, 32, 16)
    assert spec["o_block"] == (1, 1, 4, 16)
    assert spec["kv_block_hbm_reads_per_group"] == 1
    # total KV-block fetches = grid size = B * Hkv * nk (Hq-independent)
    b, h, nk = spec["grid"]
    assert b * h * nk == 2 * 2 * 2


@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("window", [None, 9])
def test_decode_kernel_gqa_groups_match_ref(group, window):
    """Regrouped kernel vs the jnp oracle for GQA group sizes 1, 4, 8."""
    B, Hkv, W, hd = 2, 2, 40, 16
    Hq = group * Hkv
    ks = jax.random.split(jax.random.PRNGKey(group * 31 + (window or 0)), 3)
    q = jax.random.normal(ks[0], (B, Hq, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, W, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, W, hd), jnp.float32)
    n_valid = 29
    kpos = jnp.broadcast_to(jnp.arange(W)[None], (B, W))
    kpos = jnp.where(kpos < n_valid, kpos, -1)
    qpos = jnp.full((B,), n_valid - 1)
    got = ops.decode_attention(q, k, v, qpos, kpos, window,
                               impl="pallas_interpret", block_k=16)
    want = ref.decode_attention(q, k, v, qpos, kpos, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
