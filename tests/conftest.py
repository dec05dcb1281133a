import os

# Keep unit tests single-device (the 512-device override belongs ONLY to
# launch/dryrun.py, which sets XLA_FLAGS before importing jax itself).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def require_host_devices(n: int) -> None:
    """Skip (never vacuously pass) a test that needs ``n`` forced host
    devices. XLA_FLAGS must be set before the FIRST jax import of the
    process — an in-test os.environ write silently no-ops once jax is
    initialized, which is exactly the failure mode this guard replaces —
    so multi-device suites run in a dedicated invocation (`make sharded`,
    the CI `sharded` step, or an 8-device subprocess)."""
    if jax.device_count() < n:
        pytest.skip(
            f"needs {n} host devices, have {jax.device_count()}; run with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} set "
            "before the first jax import (e.g. `make sharded`)")


@pytest.fixture
def host_devices():
    """Fixture form of :func:`require_host_devices` — usage:
    ``host_devices(4)`` at the top of a multi-device test."""
    return require_host_devices


def make_extras(cfg, batch, seq, key=None, dtype=jnp.float32):
    """Modality extras required by a config's family (stub frontends)."""
    from repro.models import frontend
    key = key if key is not None else jax.random.PRNGKey(42)
    extras = {}
    if cfg.family == "vlm":
        extras["image_embeds"] = frontend.vision_embeddings(
            key, batch, cfg.n_image_tokens, cfg.d_model, dtype)
    elif cfg.family == "audio":
        extras["frames"] = frontend.audio_frames(
            key, batch, cfg.encoder_seq, cfg.d_model, dtype)
    elif cfg.family == "moe" and cfg.attn_chunk is not None:
        # llama4 early fusion
        n_img = min(8, seq)
        extras["image_embeds"] = frontend.vision_embeddings(
            key, batch, n_img, cfg.d_model, dtype)
        extras["image_positions"] = frontend.image_positions(batch, n_img, seq)
    return extras


def tiny_latent_config(name="tiny-latent", vocab=256):
    """DeepSeek-V2's block at a size the CPU holds: one dense MLA layer,
    then two MLA layers with 8 of 16 routed experts held (top 3, dropless,
    gates not renormalised) and 2 shared experts; YaRN rope."""
    from repro.models import MLAConfig, MoEConfig, ModelConfig, YarnScaling
    return ModelConfig(
        name=name, family="moe", n_layers=3, d_model=64, n_heads=4,
        n_kv_heads=4, d_ff=128, vocab=vocab, dtype="float32",
        block_pattern=("mla",) + ("mla_moe",) * 2,
        mla=MLAConfig(q_lora_rank=None, kv_lora_rank=32,
                      qk_nope_head_dim=16, qk_rope_head_dim=8,
                      v_head_dim=16),
        moe=MoEConfig(n_experts=16, top_k=3, d_ff_expert=32,
                      n_shared_experts=2, dropless=True, n_held_experts=8),
        rope_scaling=YarnScaling(), norm_eps=1e-6, vocab_pad_multiple=8)


def tiny_model(kind, name, vocab=256):
    """(cfg, model, params) of the suites' tiny models: ``dense`` (GQA 8/2,
    two layers) or ``latent`` (``tiny_latent_config``)."""
    from repro.models import Model, ModelConfig
    from repro.models.config import repeat_pattern
    if kind == "latent":
        cfg = tiny_latent_config(name, vocab)
    else:
        cfg = ModelConfig(
            name=name, family="dense", n_layers=2, d_model=64, n_heads=8,
            n_kv_heads=2, d_ff=128, vocab=vocab, dtype="float32",
            block_pattern=repeat_pattern(("dense",), 2),
            vocab_pad_multiple=8)
    m = Model(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(0))
