"""Per-architecture smoke tests: reduced same-family variants run one
forward/train step and one prefill+decode step on CPU; output shapes and
finiteness are asserted. (Full configs are exercised only via the dry-run.)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import Model

from conftest import make_extras

BATCH, SEQ = 2, 16


@pytest.fixture(scope="module")
def built(request):
    cache = {}

    def build(arch):
        if arch not in cache:
            cfg = get_config(arch, "smoke")
            m = Model(cfg)
            params = m.init(jax.random.PRNGKey(0))
            cache[arch] = (cfg, m, params)
        return cache[arch]

    return build


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_shapes_and_finite(arch, built):
    cfg, m, params = built(arch)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (BATCH, SEQ), 0, cfg.vocab)
    labels = jnp.roll(tokens, -1, axis=1).at[:, -1].set(-100)
    extras = make_extras(cfg, BATCH, SEQ)
    batch = dict(tokens=tokens, labels=labels, **extras)

    logits, _, _ = m.forward(params, tokens, extras, mode="train")
    assert logits.shape == (BATCH, SEQ, cfg.padded_vocab)
    assert np.all(np.isfinite(np.asarray(logits, dtype=np.float32)))

    # one actual optimization-relevant step: loss + grads finite
    def loss_fn(p):
        return m.train_loss(p, batch, remat=False)[0]

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert np.isfinite(float(loss))
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree_util.tree_leaves(grads)))
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_serve_step_shapes_and_finite(arch, built):
    cfg, m, params = built(arch)
    P = 8
    tokens = jax.random.randint(jax.random.PRNGKey(2), (BATCH, P), 0, cfg.vocab)
    extras = make_extras(cfg, BATCH, P)
    last, caches = m.prefill(params, tokens, extras, max_len=P + 4)
    assert last.shape == (BATCH, cfg.padded_vocab)
    assert np.all(np.isfinite(np.asarray(last, dtype=np.float32)))
    nxt = jnp.argmax(last[:, :cfg.vocab], axis=-1)[:, None].astype(jnp.int32)
    for _ in range(2):
        lg, caches = m.decode_step(params, caches, nxt)
        assert lg.shape == (BATCH, cfg.padded_vocab)
        assert np.all(np.isfinite(np.asarray(lg, dtype=np.float32)))
        nxt = jnp.argmax(lg[:, :cfg.vocab], axis=-1)[:, None].astype(jnp.int32)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_config_is_reduced(arch):
    cfg = get_config(arch, "smoke")
    assert cfg.n_layers <= 4
    assert cfg.d_model <= 512
    if cfg.moe is not None:
        assert cfg.moe.n_experts <= 4


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    cfg = get_config(arch, "full")
    spec = {
        "deepseek-v3-671b": (61, 7168, 128, 128, 129280),
        "llama-3.2-vision-90b": (100, 8192, 64, 8, 128256),
        "seamless-m4t-large-v2": (24, 1024, 16, 16, 256206),
        "zamba2-7b": (81, 3584, 32, 32, 32000),
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 202048),
        "minicpm-2b": (40, 2304, 36, 36, 122753),
        "rwkv6-1.6b": (24, 2048, 32, 32, 65536),
        "stablelm-12b": (40, 5120, 32, 8, 100352),
        "internlm2-20b": (48, 6144, 48, 8, 92544),
        "llama3.2-1b": (16, 2048, 32, 8, 128256),
        "deepseek-v2-lite": (27, 2048, 16, 16, 102400),
    }[arch]
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.vocab) == spec
    dff = {"deepseek-v3-671b": 2048, "llama-3.2-vision-90b": 28672,
           "seamless-m4t-large-v2": 8192, "zamba2-7b": 14336,
           "llama4-maverick-400b-a17b": 8192, "minicpm-2b": 5760,
           "rwkv6-1.6b": 7168, "stablelm-12b": 13824,
           "internlm2-20b": 16384, "llama3.2-1b": 8192,
           "deepseek-v2-lite": 1408}[arch]
    if cfg.moe is not None and arch != "llama4-maverick-400b-a17b":
        assert cfg.moe.d_ff_expert == dff
    else:
        assert cfg.d_ff == dff
    if arch == "deepseek-v3-671b":
        assert cfg.moe.n_experts == 256 and cfg.moe.top_k == 8 and cfg.mtp
    if arch == "llama4-maverick-400b-a17b":
        assert cfg.moe.n_experts == 128 and cfg.moe.top_k == 1
    if arch == "deepseek-v2-lite":
        assert (cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.held) == (64, 6, 8)
        assert cfg.mla.q_lora_rank is None and cfg.d_ff == 10944
    if arch == "zamba2-7b":
        assert cfg.ssm.state_dim == 64
