"""Attention building-block unit tests: masks, RoPE, caches, kv-index map."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (not in container)")
from hypothesis import given, settings, strategies as st

from repro.models import attention as A
from repro.models import layers as L


def test_kv_index_map_plain_gqa():
    idx = A._kv_index_map(8, 2, 8, 2)
    np.testing.assert_array_equal(idx, [0, 0, 0, 0, 1, 1, 1, 1])


def test_kv_index_map_duplicated():
    # 32 q / 8 kv duplicated to 16: q i -> orig kv i//4, copies interleaved
    idx = A._kv_index_map(32, 8, 32, 16)
    orig = idx // 2
    np.testing.assert_array_equal(orig, np.arange(32) // 4)


def test_kv_index_map_llama4_case():
    # 40 q / 8 kv -> padded 48 q / 16 kv: originals must be preserved
    idx = A._kv_index_map(40, 8, 48, 16)
    orig = idx[:40] // 2
    np.testing.assert_array_equal(orig, np.arange(40) // 5)


@given(q=st.integers(0, 30), k=st.integers(-1, 30))
@settings(max_examples=40, deadline=None)
def test_bias_semantics(q, k):
    b = A.self_attn_bias(jnp.asarray([[q]]), jnp.asarray([[k]]), None, None)
    visible = (0 <= k <= q)
    assert (float(b[0, 0, 0]) == 0.0) == visible


def test_bias_window_and_chunk():
    qpos = jnp.asarray([[10]])
    kpos = jnp.asarray([[jnp.arange(12)]])[0]
    b_win = A.self_attn_bias(qpos, kpos, 4, None)[0, 0]
    vis = [i for i in range(12) if float(b_win[i]) == 0.0]
    assert vis == [7, 8, 9, 10]                      # (q-4, q]
    b_chunk = A.self_attn_bias(qpos, kpos, None, 4)[0, 0]
    vis = [i for i in range(12) if float(b_chunk[i]) == 0.0]
    assert vis == [8, 9, 10]                         # same chunk [8, 12)


def test_rope_relative_shift_invariance():
    """RoPE: scores depend only on relative positions."""
    hd = 16
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 1, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, hd))

    def score(qpos, kpos):
        qr = L.apply_rope(q, jnp.asarray([[qpos]]), 1.0, 10000.0)
        kr = L.apply_rope(k, jnp.asarray([[kpos]]), 1.0, 10000.0)
        return float(jnp.sum(qr * kr))

    assert score(5, 3) == pytest.approx(score(105, 103), rel=1e-4)
    assert score(5, 3) != pytest.approx(score(5, 4), rel=1e-3)


def test_partial_rotary_preserves_tail():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 1, 16))
    y = L.apply_rope(x, jnp.asarray([[3, 4]]), 0.5, 10000.0)
    np.testing.assert_allclose(np.asarray(y[..., 8:]), np.asarray(x[..., 8:]))
    assert not np.allclose(np.asarray(y[..., :8]), np.asarray(x[..., :8]))


def test_ring_buffer_prefill_keeps_last_window():
    class Cfg:
        sliding_window = 4
        n_kv_heads_padded = 1
        head_dim_ = 2
        dtype = "float32"

    cache = A.init_kv_cache(Cfg(), 1, 10)
    assert cache["k"].shape == (1, 4, 1, 2)
    S = 7
    k = jnp.arange(S, dtype=jnp.float32)[None, :, None, None] * jnp.ones((1, S, 1, 2))
    pos = jnp.arange(S)[None]
    c = A.prefill_write_cache(cache, k, k, pos)
    stored = sorted(np.asarray(c["pos_ids"][0]).tolist())
    assert stored == [3, 4, 5, 6]                     # last window survives
    assert int(c["length"][0]) == 7
    # slot of token j is j % W
    for slot, p in enumerate(np.asarray(c["pos_ids"][0])):
        assert p % 4 == slot
        assert float(c["k"][0, slot, 0, 0]) == float(p)


def test_decode_write_advances_ring():
    class Cfg:
        sliding_window = None
        n_kv_heads_padded = 1
        head_dim_ = 2
        dtype = "float32"

    cache = A.init_kv_cache(Cfg(), 2, 4)
    k1 = jnp.ones((2, 1, 1, 2))
    c = A.decode_write_cache(cache, k1, k1)
    assert np.asarray(c["length"]).tolist() == [1, 1]
    assert np.asarray(c["pos_ids"][:, 0]).tolist() == [0, 0]


def test_flash_vs_direct_mixed_value_dim():
    B, H, S, hdk, hdv = 1, 2, 8, 12, 6
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hdk))
    k = jax.random.normal(ks[1], (B, S, H, hdk))
    v = jax.random.normal(ks[2], (B, S, H, hdv))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    bias = A.self_attn_bias(pos, pos, None, None)[:, None]
    a = A._direct_attention(q, k, v, bias)
    b = A._flash_attention(q, k, v, pos, pos, None, None)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


# ---- paged decode: pool-direct scoring against the gathered view -------

PD_B, PD_M, PD_PS, PD_HKV, PD_G, PD_HD = 3, 4, 4, 2, 2, 8
PD_W = PD_M * PD_PS                              # 16 logical rows per slot

# (block table, cached length) per slot and the pool's page count; the
# new token of each slot lands at position ``length``. A slot whose next
# row has no page coasts: its write lands in the trash page.
PD_LAYOUTS = {
    # page 2 holds logical page 0 of slots 0 and 1 (a shared prefix)
    "shared_page": ([[2, 5, -1, -1], [2, 7, -1, -1], [1, -1, -1, -1]],
                    [6, 5, 2], 11),
    # page 9 was recycled: its rows past slot 0's length hold stale keys
    "recycled_stale_rows": ([[4, 9, -1, -1], [0, 3, 8, -1],
                             [10, -1, -1, -1]], [5, 9, 1], 11),
    # unmapped entries between and after mapped pages
    "unmapped_entries": ([[3, -1, 6, -1], [-1, -1, -1, 0],
                          [7, 1, -1, -1]], [2, 0, 6], 11),
    # slot 2 holds no page: its write and its reads hit the trash page
    "slot_without_pages": ([[6, 2, -1, -1], [5, -1, -1, -1],
                            [-1, -1, -1, -1]], [7, 3, 0], 11),
    # two coasting slots write the trash page in the same step
    "trash_writes": ([[8, 4, 0, -1], [-1, -1, -1, -1], [-1, -1, -1, -1]],
                     [10, 4, 9], 11),
    # the equal-memory pool, (B x M + 1) pages, more rows than B x W
    "equal_memory_pool": ([[2, 5, -1, -1], [2, 7, 11, -1], [1, 0, 3, 4]],
                          [6, 9, 13], PD_B * PD_M),
}


def _pd_case(name, dtype):
    tbl, length, pages = PD_LAYOUTS[name]
    tbl = np.asarray(tbl, np.int32)
    length = np.asarray(length, np.int32)
    lp = np.arange(PD_W)
    mapped = tbl[:, lp // PD_PS] >= 0
    pos_ids = np.where((lp[None] < length[:, None]) & mapped, lp[None], -1)
    ks = jax.random.split(jax.random.PRNGKey(sum(map(ord, name))), 5)
    pool = (PD_HKV, pages + 1, PD_PS, PD_HD)
    cache = {"k_pages": jax.random.normal(ks[0], pool).astype(dtype),
             "v_pages": jax.random.normal(ks[1], pool).astype(dtype),
             "pos_ids": jnp.asarray(pos_ids, jnp.int32),
             "length": jnp.asarray(length)}
    q = jax.random.normal(ks[2], (PD_B, 1, PD_HKV * PD_G, PD_HD)).astype(dtype)
    k = jax.random.normal(ks[3], (PD_B, 1, PD_HKV, PD_HD)).astype(dtype)
    v = jax.random.normal(ks[4], (PD_B, 1, PD_HKV, PD_HD)).astype(dtype)
    # a slot is live when the page of its new row is mapped
    live = tbl[np.arange(PD_B), length // PD_PS] >= 0
    return cache, jnp.asarray(tbl), q, k, v, jnp.asarray(length)[:, None], live


def _pd_cfg():
    from repro.models import ModelConfig
    return ModelConfig(name="tiny-pool", family="dense", n_layers=1,
                       d_model=PD_HKV * PD_G * PD_HD, n_heads=PD_HKV * PD_G,
                       n_kv_heads=PD_HKV, d_ff=32, vocab=64,
                       dtype="float32", block_pattern=("dense",),
                       vocab_pad_multiple=8)


def _pd_gathered(cfg, cache, tbl, q, k, v, pos, dtype=None):
    """The gather path: each slot's logical (W, hd) view copied out of the
    pool into a contiguous cache, decoded there (optionally upcast)."""
    contig = {"k": A.gather_pages(cache["k_pages"], tbl),
              "v": A.gather_pages(cache["v_pages"], tbl),
              "pos_ids": cache["pos_ids"], "length": cache["length"]}
    if dtype is not None:
        contig, q, k, v = jax.tree_util.tree_map(
            lambda x: x.astype(dtype) if x.dtype == jnp.bfloat16 else x,
            (contig, q, k, v))
    out, _ = A._cached_attention(cfg, q, k, v, pos, None, None, contig,
                                 "decode", None, False)
    return np.asarray(out.astype(jnp.float32))


@pytest.mark.parametrize("layout", sorted(PD_LAYOUTS))
def test_pool_direct_decode_matches_gather(layout, monkeypatch):
    """Paged decode attention scores the page pool in place, whatever its
    size against B x W, and equals each slot's gathered logical view on
    every live slot: f32 to float tolerance, bf16 within the gather
    path's own rounding. A slot with no page for its new row (a coasting
    slot, whose token is never emitted) reads a finite value."""
    cfg = _pd_cfg()
    assert A.uniform_gqa_group(cfg) == PD_G
    calls = []
    pool_attention = A._pool_decode_attention
    monkeypatch.setattr(A, "_pool_decode_attention",
                        lambda *a: calls.append(1) or pool_attention(*a))

    def attend(dtype):
        cache, tbl, q, k, v, pos, live = _pd_case(layout, dtype)
        out, _ = A._cached_attention(cfg, q, k, v, pos, None, None, cache,
                                     "decode", tbl, False)
        want = _pd_gathered(cfg, cache, tbl, q, k, v, pos)
        exact = _pd_gathered(cfg, cache, tbl, q, k, v, pos, jnp.float32)
        return np.asarray(out.astype(jnp.float32)), want, exact, live

    got, want, _, live = attend(jnp.float32)
    assert calls == [1]
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[live], want[live], rtol=1e-5, atol=1e-6)

    # bf16: the two paths differ by less than the gather path's own
    # rounding against its f32 result on the same (bf16-valued) inputs
    got16, want16, exact, live = attend(jnp.bfloat16)
    rounding = np.abs(want16 - exact)[live].max()
    assert rounding > 0
    assert np.abs(got16 - want16)[live].max() <= rounding
    assert np.isfinite(got16).all()
