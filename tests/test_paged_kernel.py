"""Paged decode-attention kernel: interpret-mode sweep vs the jnp oracle
across GQA group sizes and page sizes, plus the extended decode_grid_spec
contract — the block-table indirection must preserve the contiguous
kernel's one-HBM-read-per-(batch, kv head, kv block) traffic shape.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.models.attention import gather_pages


def make_pool(rng, B, Hkv, hd, ps, num_pages, lens, max_pages):
    """Random pool + a valid block table mapping each slot's pages."""
    kp = jnp.asarray(rng.normal(size=(Hkv, num_pages + 1, ps, hd)),
                     jnp.float32)
    vp = jnp.asarray(rng.normal(size=(Hkv, num_pages + 1, ps, hd)),
                     jnp.float32)
    perm = rng.permutation(num_pages)
    tbl = np.full((B, max_pages), -1, np.int32)
    pi = 0
    for b, L in enumerate(lens):
        npg = -(-L // ps)
        tbl[b, :npg] = perm[pi:pi + npg]
        pi += npg
    kpos = np.full((B, max_pages * ps), -1, np.int32)
    for b, L in enumerate(lens):
        kpos[b, :L] = np.arange(L)
    qpos = jnp.asarray([L - 1 for L in lens], jnp.int32)
    return kp, vp, jnp.asarray(tbl), qpos, jnp.asarray(kpos)


@pytest.mark.parametrize("group", [1, 2, 4])
@pytest.mark.parametrize("page_size", [8, 16])
def test_paged_kernel_matches_ref(group, page_size):
    B, Hkv, hd, M = 3, 2, 16, 4
    Hq = group * Hkv
    num_pages = B * M - 2              # tighter than B*M: pages are shared
    lens = [13, 3 * page_size, 5]      # partial page, exact fill, tiny
    rng = np.random.default_rng(group * 17 + page_size)
    kp, vp, tbl, qpos, kpos = make_pool(rng, B, Hkv, hd, page_size,
                                        num_pages, lens, M)
    q = jnp.asarray(rng.normal(size=(B, Hq, hd)), jnp.float32)
    got = ops.paged_decode_attention(q, kp, vp, tbl, qpos, kpos,
                                     impl="pallas_interpret")
    want = ref.paged_decode_attention(q, kp, vp, tbl, qpos, kpos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # and the oracle itself equals contiguous attention on the gathered view
    kk = jnp.moveaxis(gather_pages(kp, tbl), 1, 2)     # (B, Hkv, W, hd)
    vv = jnp.moveaxis(gather_pages(vp, tbl), 1, 2)
    base = ref.decode_attention(q, kk, vv, qpos, kpos)
    np.testing.assert_allclose(np.asarray(want), np.asarray(base), rtol=1e-6)


@pytest.mark.parametrize("window", [None, 9])
def test_paged_kernel_masking(window):
    """Sliding-window masking composes with page indirection."""
    B, Hq, Hkv, hd, ps, M = 2, 4, 2, 16, 8, 3
    rng = np.random.default_rng(11)
    kp, vp, tbl, qpos, kpos = make_pool(rng, B, Hkv, hd, ps, B * M, [17, 9],
                                        M)
    q = jnp.asarray(rng.normal(size=(B, Hq, hd)), jnp.float32)
    got = ops.paged_decode_attention(q, kp, vp, tbl, qpos, kpos,
                                     window=window, impl="pallas_interpret")
    want = ref.paged_decode_attention(q, kp, vp, tbl, qpos, kpos,
                                      window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("page_size", [8, 16])
def test_extended_decode_grid_spec(page_size):
    """The paged grid keeps the GQA-grouped traffic contract: kv axis
    iterates logical pages, one (kv head, physical page) pair per block,
    whole query group per program."""
    B, Hq, Hkv, hd, M, P = 2, 8, 2, 16, 4, 6
    spec = ops.decode_grid_spec(B, Hq, Hkv, W=M * page_size, hd=hd, hd_v=hd,
                                page_size=page_size, num_pages=P)
    assert spec["paged"] is True
    assert spec["grid"] == (B, Hkv, M)          # (B, Hkv, nk) — NOT Hq
    assert spec["group"] == 4
    assert spec["q_block"] == (1, 1, 4, hd)     # whole GQA group rides along
    assert spec["k_block"] == (1, 1, page_size, hd)   # ONE page, ONE kv head
    assert spec["v_block"] == (1, 1, page_size, hd)
    assert spec["o_block"] == (1, 1, 4, hd)
    assert spec["num_kv_blocks"] == M
    assert spec["page_size"] == page_size
    assert spec["kv_pool_shape"] == (Hkv, P + 1, page_size)  # +1 trash page
    assert spec["kv_block_hbm_reads_per_group"] == 1
    # total page fetches = grid size, independent of Hq
    b, h, nk = spec["grid"]
    assert b * h * nk == B * Hkv * M
    # the contiguous spec is unchanged by the extension
    assert ops.decode_grid_spec(B, Hq, Hkv, 64, hd, hd)["paged"] is False


def test_unmapped_pages_never_contribute():
    """A slot whose table maps only its first page must score identically
    whether the rest of the pool holds garbage or zeros — the trash-page
    redirect plus logical -1 positions hide every unmapped row."""
    B, Hq, Hkv, hd, ps, M = 1, 4, 2, 16, 8, 3
    rng = np.random.default_rng(5)
    kp, vp, tbl, qpos, kpos = make_pool(rng, B, Hkv, hd, ps, 4, [6], M)
    q = jnp.asarray(rng.normal(size=(B, Hq, hd)), jnp.float32)
    base = ops.paged_decode_attention(q, kp, vp, tbl, qpos, kpos,
                                      impl="pallas_interpret")
    # poison every physical page the table does NOT map (incl. trash)
    mapped = {int(p) for p in np.asarray(tbl).ravel() if p >= 0}
    poison = np.asarray(kp).copy()
    for p in range(kp.shape[1]):
        if p not in mapped:
            poison[:, p] = 1e3
    got = ops.paged_decode_attention(q, jnp.asarray(poison), vp, tbl,
                                     qpos, kpos, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(base), rtol=1e-6)
