"""The comparison that decides ``correct``: a sound run passes it; the
lower-precision control and a timed path broken underneath fail it."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import perfbench_tiny as tiny


def _greedy(ref, cfg, weights, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(ref.logits(cfg, weights, seq, [len(seq) - 1])[0]
                       .argmax()))
    return seq[len(prompt):]


@pytest.mark.parametrize("seed", [3, 2**33 + 1, 123456789])
def test_control_fails_and_reference_passes(seed):
    from harness import model as M, oracle
    cfg = dict(tiny.CONFIG, torch_dtype="bfloat16")
    weights = M.make_weights(cfg, seed)
    ref = oracle.load_reference(tiny.BENCH_DIR, cfg["architecture"])
    rng = np.random.default_rng(seed)

    class Req:
        def __init__(self, rid, prompt, tokens):
            self.rid, self.prompt, self.tokens = rid, prompt, tokens

    reqs = []
    for rid in range(3):
        prompt = rng.integers(0, cfg["vocab_size"], 40 + 10 * rid).tolist()
        reqs.append(Req(rid, prompt, _greedy(ref, cfg, weights, prompt, 40)))
    limit = tiny.CONFIG["check"]["widest_logit_gap"]
    assert oracle.widest_gap(ref, cfg, weights, reqs) <= limit
    assert oracle.widest_gap(ref, cfg, weights, reqs, control=True) > limit


def test_sound_run_is_correct():
    out = tiny.run_tiny(seed=2**32 + 17)
    assert out["checks"]["widest_logit_gap"]["value"] is not None
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0


def _alter_token(monkeypatch):
    from repro.serving import sampling
    real = sampling.sample

    def second_best(logits, key, temperature):
        top = real(logits, key, temperature)
        return real(logits.at[jnp.arange(logits.shape[0]), top]
                    .set(-jnp.inf), key, temperature)
    monkeypatch.setattr(sampling, "sample", second_best)


def _keep_state(monkeypatch):
    from repro.models import attention
    monkeypatch.setattr(attention, "paged_decode_write",
                        lambda cache, tbl, k1, v1: cache)


@pytest.mark.parametrize("fault", [_alter_token, _keep_state],
                         ids=["token_altered", "decode_state_unchanged"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    jax.clear_caches()              # the faults act while programs trace
    fault(monkeypatch)
    try:
        out = tiny.run_tiny(seed=5)
    finally:
        jax.clear_caches()
    assert not out["correct"], out["checks"]
    assert out["checks"]["widest_logit_gap"]["value"] > \
        out["checks"]["widest_logit_gap"]["limit"]


def test_an_engine_failure_ends_the_run(monkeypatch):
    """A quantum that raises inside the server's drive task stops the run
    with that error instead of leaving the clients waiting."""
    from repro.serving import engine

    def broken(self, max_steps):
        raise RuntimeError("device lost")
    monkeypatch.setattr(engine.ServingEngine, "_decode_chunk", broken)
    with pytest.raises(RuntimeError, match="engine failed"):
        tiny.run_tiny(seed=9)
