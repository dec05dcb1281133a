"""An architecture enters the benchmark as files: ``harness.model`` and
``harness.counts`` load ``architectures/<architecture>.py`` by the
configuration's name, make the same weights as before the move, refuse an
architecture that has no module, and drive a whole run through a module
that is not the dense decoder's."""
from __future__ import annotations

import json
import math
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import perfbench_tiny as tiny
from harness import counts, model as M, spec


# ``harness.model`` before the architecture modules, kept as the
# reference the move is held to
def _old_canonical_shapes(cfg):
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    L, ff, hd = cfg["num_hidden_layers"], cfg["intermediate_size"], d // hq
    vp = -(-cfg["vocab_size"] // cfg["assumed"]["vocab_pad_multiple"]) \
        * cfg["assumed"]["vocab_pad_multiple"]
    q, kv = hq * hd, cfg["num_key_value_heads"] * hd
    shapes = {"embed": (vp, d), "final_norm": (d,),
              "ln1": (L, d), "wq": (L, d, q), "wk": (L, d, kv),
              "wv": (L, d, kv), "wo": (L, q, d), "ln2": (L, d),
              "w_gate": (L, d, ff), "w_up": (L, d, ff), "w_down": (L, ff, d)}
    if not cfg["tie_word_embeddings"]:
        shapes["lm_head"] = (d, vp)
    return shapes


def _old_std(cfg, name, shape):
    if name in ("ln1", "ln2", "final_norm"):
        return None
    return (float(cfg["assumed"]["embedding_std"]) if name == "embed"
            else 1.0 / math.sqrt(shape[-2]))


def _old_make_weights(cfg, seed):
    shapes = _old_canonical_shapes(cfg)
    dt = jnp.dtype(cfg["torch_dtype"])
    init_std = float(cfg["assumed"]["embedding_std"])

    def build(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            if name in ("ln1", "ln2", "final_norm"):
                out[name] = jnp.ones(shape, dt)
                continue
            std = init_std if name == "embed" else 1.0 / math.sqrt(shape[-2])
            out[name] = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                           dt) * jnp.asarray(std, dt))
        return out

    return jax.jit(build)(M.key_from_seed(seed, 0))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("tied", [True, False])
def test_weights_are_bit_identical_to_the_old_path(dtype, tied):
    cfg = dict(tiny.CONFIG, torch_dtype=dtype, tie_word_embeddings=tied)
    new, old = M.make_weights(cfg, 2**33 + 21), _old_make_weights(
        cfg, 2**33 + 21)
    assert list(new) == list(old)
    for name in old:
        assert new[name].dtype == old[name].dtype, name
        assert np.array_equal(np.asarray(new[name]).view(np.uint8),
                              np.asarray(old[name]).view(np.uint8)), name


@pytest.mark.parametrize("name", ["minicpm-2b", "internlm2-20b-pp8"])
def test_configurations_keep_their_tensors_and_draws(name):
    """Same names, so the same sorted order and ``fold_in`` indices, and the
    same shapes and spreads as before the move."""
    cfg = json.loads((tiny.BENCH_DIR / "configs" / f"{name}.json")
                     .read_text())
    shapes = M.canonical_shapes(cfg)
    assert shapes == _old_canonical_shapes(cfg)
    arch = M.architecture(cfg)
    for t, shape in shapes.items():
        assert arch.init_std(cfg, t, shape) == _old_std(cfg, t, shape), t


def test_an_unknown_architecture_names_its_missing_module():
    cfg = dict(tiny.CONFIG, architecture="no_such_block")
    want = str(tiny.BENCH_DIR / "architectures" / "no_such_block.py")
    for call in (lambda: M.make_weights(cfg, 1),
                 lambda: M.program_config(cfg),
                 lambda: counts.memory(cfg),
                 lambda: counts.decode_step(cfg, [3])):
        with pytest.raises(FileNotFoundError) as err:
            call()
        assert want in str(err.value)


# A second architecture, written as files only: the dense block under its
# own tensor names (so its own draws), with a reference that reads them.
TOY_ARCH = '''
"""Toy architecture for the harness's tests: a dense decoder whose
tensors carry their own names."""
import importlib.util

_spec = importlib.util.spec_from_file_location("toy_dense", {dense!r})
dense = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(dense)

RENAME = {{"wq": "q_proj", "wk": "k_proj", "wv": "v_proj", "wo": "o_proj",
          "w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}}
BACK = {{v: k for k, v in RENAME.items()}}
CALLS = []


def canonical_shapes(cfg):
    CALLS.append("canonical_shapes")
    return {{RENAME.get(k, k): v
            for k, v in dense.canonical_shapes(cfg).items()}}


def init_std(cfg, name, shape):
    return dense.init_std(cfg, BACK.get(name, name), shape)


def program_config(cfg):
    CALLS.append("program_config")
    return dense.program_config(cfg)


def program_params(weights):
    CALLS.append("program_params")
    return dense.program_params({{BACK.get(k, k): v
                                 for k, v in weights.items()}})


dims = dense.dims
parameters = dense.parameters
kv_bytes_per_token = dense.kv_bytes_per_token
decode_step = dense.decode_step
prefill_chunk = dense.prefill_chunk
decode_attention = dense.decode_attention
memory = dense.memory
'''

TOY_REFERENCE = '''
import importlib.util

_spec = importlib.util.spec_from_file_location("toy_dense_ref", {dense!r})
dense = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(dense)

BACK = {{"q_proj": "wq", "k_proj": "wk", "v_proj": "wv", "o_proj": "wo",
        "gate_proj": "w_gate", "up_proj": "w_up", "down_proj": "w_down"}}


def logits(cfg, weights, tokens, at, fp8=False):
    return dense.logits(cfg, {{BACK.get(k, k): v
                              for k, v in weights.items()}}, tokens, at,
                        fp8=fp8)
'''


def test_a_new_architecture_enters_as_files(tmp_path, monkeypatch):
    for sub, text, dense in (
            ("architectures", TOY_ARCH, "architectures/dense_decoder.py"),
            ("references", TOY_REFERENCE, "references/dense_decoder.py")):
        (tmp_path / sub).mkdir()
        (tmp_path / sub / "toy_decoder.py").write_text(textwrap.dedent(
            text.format(dense=str(tiny.BENCH_DIR / dense))))
    monkeypatch.setattr(spec, "BENCH_DIR", tmp_path)
    cfg = dict(tiny.CONFIG, architecture="toy_decoder")
    monkeypatch.setattr(tiny, "CONFIG", cfg)
    toy = M.architecture(cfg)
    assert toy.__file__ == str(tmp_path / "architectures" / "toy_decoder.py")
    out = tiny.run_tiny(seed=2**31 + 77)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert {"canonical_shapes", "program_config", "program_params"} \
        <= set(toy.CALLS)
    assert counts.memory(cfg) == toy.dense.memory(cfg)
    assert "q_proj" in M.make_weights(cfg, 1)
