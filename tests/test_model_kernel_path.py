"""Model-integrated Pallas path: cfg.attn_impl='pallas_interpret' must match
the ref path bit-for-bit (within fp tolerance) through the full model, for
train, prefill, and ring-buffer decode."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import Model, ModelConfig
from repro.models.config import repeat_pattern


def pair(cfg):
    m_ref = Model(cfg)
    m_ker = Model(dataclasses.replace(cfg, attn_impl="pallas_interpret"))
    params = m_ref.init(jax.random.PRNGKey(0))
    return m_ref, m_ker, params


@pytest.mark.parametrize("window,chunk,gae", [
    (None, None, 0), (6, None, 0), (None, 4, 2)])
def test_kernel_path_parity(window, chunk, gae):
    cfg = ModelConfig(
        name="kp", family="dense", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab=128, dtype="float32",
        sliding_window=window, attn_chunk=chunk, global_attn_every=gae,
        block_pattern=repeat_pattern(("dense",), 2), vocab_pad_multiple=8)
    m_ref, m_ker, params = pair(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 128)
    a, _, _ = m_ref.forward(params, tokens, mode="train")
    b, _, _ = m_ker.forward(params, tokens, mode="train")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-4, atol=2e-4)
    la, ca = m_ref.prefill(params, tokens[:, :8], max_len=12)
    lb, cb = m_ker.prefill(params, tokens[:, :8], max_len=12)
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                               rtol=2e-4, atol=2e-4)
    for i in range(3):
        la, ca = m_ref.decode_step(params, ca, tokens[:, 8 + i:9 + i])
        lb, cb = m_ker.decode_step(params, cb, tokens[:, 8 + i:9 + i])
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=2e-4, atol=2e-4)


def test_kernel_path_falls_back_for_nonuniform_heads():
    """llama4-style padded-q mapping is non-uniform: the kernels cannot
    take it, so asking for them raises instead of silently running the
    ref path (the ref path itself still serves the config)."""
    cfg = ModelConfig(
        name="kp2", family="dense", n_layers=1, d_model=40, n_heads=5,
        n_kv_heads=1, d_ff=64, vocab=64, dtype="float32", head_dim=8,
        pad_heads_to_multiple=6,
        block_pattern=("dense",), vocab_pad_multiple=8)
    from repro.models.attention import uniform_gqa_group
    assert uniform_gqa_group(cfg) is None
    m_ref, m_ker, params = pair(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 6), 0, 64)
    a, _, _ = m_ref.forward(params, tokens, mode="train")
    assert np.isfinite(np.asarray(a)).all()
    with pytest.raises(ValueError, match="uniform GQA"):
        m_ker.forward(params, tokens, mode="train")
