"""A configuration file's model, as the program serves it.

The benchmark builds the program's ``ModelConfig`` from its own
configuration file (not from ``repro.configs``), so a change to the
program's presets cannot move the yardstick. Weights are random and made
by the benchmark, on the device, in one jitted call from the seed, under
the benchmark's own names (``canonical_shapes``). The program's parameter
tree is assembled from the same arrays, and the reference reads the
canonical names, so both see the same numbers and the reference takes
nothing the program made.

What depends on the architecture lives in ``architectures/<name>.py``,
loaded by the configuration file's ``architecture``; its contract is in
``architectures/dense_decoder.py``.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness import spec
from repro.models import Model, ModelConfig

_LOADED: Dict[Path, object] = {}


def architecture(cfg: Dict):
    """The module ``architectures/<cfg["architecture"]>.py`` under the
    benchmark's directory, loaded once per path."""
    name = cfg["architecture"]
    path = spec.BENCH_DIR / "architectures" / f"{name}.py"
    if path not in _LOADED:
        _LOADED[path] = spec.load_module(path, f"perfbench_arch_{name}")
    return _LOADED[path]


def dims(cfg: Dict) -> Dict[str, int]:
    """The shape numbers every counter and the reference need."""
    return architecture(cfg).dims(cfg)


def canonical_shapes(cfg: Dict) -> Dict[str, tuple]:
    return architecture(cfg).canonical_shapes(cfg)


def program_config(cfg: Dict) -> ModelConfig:
    """The program's ModelConfig for a configuration file."""
    return architecture(cfg).program_config(cfg)


def dtype_of(cfg: Dict):
    return jnp.dtype(cfg["torch_dtype"])


def key_from_seed(seed: int, stream: int) -> jax.Array:
    """A PRNG key for ``stream`` of a run, from a seed of any size."""
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0] >> 1)),
                              int(words[1] >> 1))


def make_weights(cfg: Dict, seed: int,
                 sharding: Optional[jax.sharding.Sharding] = None
                 ) -> Dict[str, jax.Array]:
    """Every weight in one jitted call, in the served dtype: tensor number
    i of the sorted names from ``fold_in(key, i)``, by the architecture's
    ``init_std`` (None: ones). With ``sharding`` (a replicated sharding
    over a fleet's mesh) each chip draws its own replica in the same call;
    without, the weights land on the default device."""
    arch = architecture(cfg)
    shapes = arch.canonical_shapes(cfg)
    dt = dtype_of(cfg)

    def build(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            std = arch.init_std(cfg, name, shape)
            if std is None:
                out[name] = jnp.ones(shape, dt)
                continue
            out[name] = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                           dt) * jnp.asarray(std, dt))
        return out

    if sharding is None:
        return jax.jit(build)(key_from_seed(seed, 0))
    return jax.jit(build, out_shardings=sharding)(key_from_seed(seed, 0))


def one_replica(weights: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """The first chip's copy of replicated weights (no copy is made)."""
    return {k: (v.addressable_shards[0].data
                if len(v.sharding.device_set) > 1 else v)
            for k, v in weights.items()}


def program_params(cfg: Dict, weights: Dict[str, jax.Array],
                   model: Model) -> Dict:
    """The program's parameter tree over the same arrays (no copy), checked
    leaf for leaf against the program's own parameter shapes."""
    tree = architecture(cfg).program_params(weights)
    want = model.param_shapes()
    got = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    if (jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(got)
            or jax.tree_util.tree_leaves(want) != jax.tree_util.tree_leaves(got)):
        raise ValueError(f"{model.cfg.name}: the program's parameter tree no "
                         f"longer matches the benchmark's mapping:\n{want}\n"
                         f"vs\n{got}")
    return tree
