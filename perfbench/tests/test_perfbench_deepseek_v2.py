"""DeepSeek-V2 in the benchmark: the program's chunked prefill and then
paged decode against ``references/deepseek_v2.py`` on seeded random
weights, the departures that comparison must catch, a whole tiny run
through ``architectures/deepseek_v2.py``, and pins of that module's counts
and of the expert layer's readers."""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import perfbench_tiny as tiny
from harness import counts, model as M, moe_readings, oracle, spec
from harness import program_trace as PT
from harness.readings import Context
from harness.trace import Event
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as MOE
from repro.serving import engine as E
from repro.serving import paged

# DeepSeek-V2's published config at a size the CPU holds (float32, so the
# program and the reference compute in the same precision): 1 dense and 2
# expert layers, d 64, 4 heads, latent 32, rope 8, nope 16, v 16, 8 held
# of 16 routed experts, top 3, 2 shared; the published YaRN numbers
CONFIG = {
    "name": "tiny-deepseek", "architecture": "deepseek_v2",
    "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": None, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 8,
    "n_shared_experts": 2, "num_experts_per_tok": 3,
    "scoring_func": "softmax", "topk_method": "greedy",
    "norm_topk_prob": False, "routed_scaling_factor": 1,
    "rope_theta": 10000, "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"},
    "rms_norm_eps": 1e-6, "vocab_size": 4000, "tie_word_embeddings": False,
    "torch_dtype": "float32",
    "published": {"n_routed_experts": 16},
    "assumed": {"pad_heads_to_multiple": 1, "vocab_pad_multiple": 8,
                "embedding_std": 0.02},
    "engine": dict(tiny.CONFIG["engine"]),
    # float32 program against the float32 reference: greedy tokens agree
    # and the gap reads 0 to rounding
    "check": {"widest_logit_gap": 1e-3},
}
PS, CHUNK, MAX_LEN, PAGES = 8, 16, 128, 16
SEQ, PROMPT = 72, 40          # 40 prompt tokens in chunks, 31 decode steps
# float32 on both sides: the program's online softmax, absorbed decode and
# grouped expert products sum in other orders than the reference's full
# softmax, expanded keys and dense expert products, which moves logits of
# order 1 by about 1e-6; 1e-4 is that with room, and each departure below
# moves them by more than 1e-2
TOL = 1e-4


@pytest.fixture(scope="module")
def weights():
    return M.make_weights(CONFIG, 2**32 + 5)


@pytest.fixture(scope="module")
def seq():
    return np.random.default_rng(4).integers(0, CONFIG["vocab_size"],
                                             SEQ).tolist()


@pytest.fixture(scope="module")
def reference(weights, seq):
    ref = oracle.load_reference(spec.BENCH_DIR, "deepseek_v2")
    at = np.arange(SEQ - 1)
    return ref.logits(CONFIG, weights, seq[:-1], at)


@functools.partial(jax.jit, static_argnums=0)
def _decode(model, params, caches, tok):
    caches = dict(caches)
    caches["paged"] = paged.alloc_decode_pages(
        caches["paged"], caches["t"], jnp.ones((1,), bool), PS)
    return model.decode_step(params, caches, tok)


def served_logits(weights, seq, program_cfg=None):
    """The program's logits over ``seq``: the prompt through chunked
    prefill (the last position of each chunk), then teacher-forced paged
    decode (every later position). Returns {position: logits}."""
    cfg = program_cfg or M.program_config(CONFIG)
    model = M.Model(cfg)
    params = M.program_params(CONFIG, weights, model)
    caches = paged.paginate_cache(model.init_cache(1, MAX_LEN), 1, PS,
                                  PAGES)
    slots = jnp.zeros((1,), jnp.int32)
    out = {}
    for i in range(0, PROMPT, CHUNK):
        piece = seq[i:min(i + CHUNK, PROMPT)]
        tok = np.zeros((1, CHUNK), np.int32)
        tok[0, :len(piece)] = piece
        mask = (np.arange(CHUNK) < len(piece))[None].astype(np.int32)
        _, lg, _, caches, _ = E._CHUNK_PREFILL(
            model, params, caches, jnp.asarray(tok), jnp.asarray(mask),
            slots, jax.random.PRNGKey(0), vocab=CONFIG["vocab_size"],
            temperature=0.0, page_size=PS, sharing=False)
        out[i + len(piece) - 1] = np.asarray(lg[0])
    for pos in range(PROMPT, SEQ - 1):
        lg, caches = _decode(model, params, caches,
                             jnp.asarray([[seq[pos]]], jnp.int32))
        out[pos] = np.asarray(lg[0, :CONFIG["vocab_size"]])
    return out


def widest_diff(got, reference):
    return max(float(np.abs(v - reference[p]).max()) for p, v in got.items())


def test_chunked_prefill_then_paged_decode_match_the_reference(
        weights, seq, reference):
    got = served_logits(weights, seq)
    assert sorted(got) == [15, 31, 39] + list(range(PROMPT, SEQ - 1))
    assert widest_diff(got, reference) < TOL
    # the positions past YaRN's original context carry rope that matters
    assert np.abs(reference).max() > 0.5


_HELD_PAIRS = MOE.held_pairs


def _held_with_capacity(ids, gates, n_held):
    """The capacity layer's rule put into the dropless layer: a pair past
    its expert's capacity (tokens x top_k x 1.25 / experts, at least 8)
    weighs nothing."""
    order, sizes, weights = _HELD_PAIRS(ids, gates, n_held)
    T, k = ids.shape
    cap = max(8, -(-T * k * 5 // (4 * n_held)))
    ends = jnp.cumsum(sizes)
    expert = jnp.searchsorted(ends, jnp.arange(T * k), side="right")
    rank = jnp.arange(T * k) - (ends - sizes)[jnp.clip(expert, 0,
                                                       n_held - 1)]
    return order, sizes, jnp.where(rank < cap, weights, 0.0)


def _renormalised(router_softmax_topk):
    def renorm(router_logits, top_k):
        gates, ids = router_softmax_topk(router_logits, top_k)
        return gates / gates.sum(axis=-1, keepdims=True), ids
    return renorm


def _unscaled_rope(rope_frequencies):
    def plain(head_dim, rotary_pct, theta, scaling=None):
        return rope_frequencies(head_dim, rotary_pct, theta)
    return plain


@pytest.mark.parametrize("departure", ["mscale_dropped", "gates_renormalised",
                                       "rope_unscaled", "capacity_drop"])
def test_each_departure_fails_the_comparison(departure, weights, seq,
                                             reference, monkeypatch):
    cfg = M.program_config(CONFIG)
    if departure == "mscale_dropped":
        plain = 1.0 / np.sqrt(cfg.mla.qk_nope_head_dim
                              + cfg.mla.qk_rope_head_dim)
        monkeypatch.setattr(A, "mla_softmax_scale", lambda c: plain)
    elif departure == "gates_renormalised":
        monkeypatch.setattr(MOE, "router_softmax_topk",
                            _renormalised(MOE.router_softmax_topk))
    elif departure == "rope_unscaled":
        monkeypatch.setattr(L, "rope_frequencies",
                            _unscaled_rope(L.rope_frequencies))
    else:
        monkeypatch.setattr(MOE, "held_pairs", _held_with_capacity)
    got = served_logits(weights, seq, cfg)
    assert widest_diff(got, reference) > 100 * TOL


def test_a_whole_tiny_run_through_the_module(monkeypatch):
    """The harness's whole run with the DeepSeek-V2 module: weights, the
    program's config and tree, serving, the reference's verdict."""
    monkeypatch.setattr(tiny, "CONFIG", CONFIG)
    out = tiny.run_tiny(seed=2**31 + 91)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["widest_logit_gap"]["value"] < 1e-3


# ------------------------------------------------------------- the counts


def _config():
    return json.loads((spec.BENCH_DIR / "configs" /
                       "deepseek-v2-lite-ep8.json").read_text())


def test_counts_of_the_chips_share():
    cfg = _config()
    mem = counts.memory(cfg)
    # attention 13,767,168 a layer, dense MLP 67,239,936, an expert layer
    # 86,638,592 (router 131,072, 8 experts 69,206,016, shared 17,301,504),
    # embedding and head 419,430,400, final norm 2,048
    assert counts.parameters(cfg) == mem["parameters"] == 3_110_989_312
    assert mem["weight_bytes"] == 6_221_978_624
    assert counts.kv_bytes_per_token(cfg) == 27 * 576 * 2 == 31_104
    assert mem["pool_bytes"] == (cfg["engine"]["num_pages"] + 1) * 16 * 31_104
    arch = M.architecture(cfg)
    assert arch.matrix_params(cfg) == {
        "attn": 13_762_560, "dense_mlp": 67_239_936, "router": 131_072,
        "shared": 17_301_504, "expert": 8_650_752}
    step = counts.decode_step(cfg, [999])
    unrouted = 27 * 13_762_560 + 67_239_936 + 26 * (131_072 + 17_301_504)
    head = 2048 * 102_400
    assert step["flops"] == 2 * (unrouted + head) + 2 * 27 * 16 * 1088 * 1000
    weights = (27 * 13_767_168 + 67_239_936 + 26 * (131_072 + 17_301_504)
               + 2048 + head) * 2
    assert step["bytes"] == weights + 1000 * 31_104
    attn = counts.decode_attention(cfg, [999, 9])
    absorb = 2 * 27 * 16 * 2 * 512 * 128
    assert attn["flops"] == 2 * absorb + 2 * 27 * 16 * 1088 * (1000 + 10)
    assert attn["bytes"] == (1001 + 11) * 31_104
    chunk = counts.prefill_chunk(cfg, 0, 2048)
    per_tok = 2 * (unrouted + 26 * 0.75 * 8_650_752)
    keys = 2048 * 2049 // 2
    assert chunk == 2048 * per_tok + 2 * 27 * 16 * 320 * keys + 2 * head
    assert counts.prefill_chunk(cfg, 0, 2048) + counts.prefill_chunk(
        cfg, 2048, 4096) == counts.prefill_chunk(cfg, 0, 4096) + 2 * head
    moe = arch.moe_decode(cfg, pairs=12, experts_hit=10, steps=1)
    assert moe == {"flops": 2 * 12 * 8_650_752 + 2 * 4 * 26 * 131_072,
                   "bytes": 10 * 8_650_752 * 2 + 26 * 131_072 * 2}


def test_configuration_states_its_cut():
    cfg = _config()
    assert cfg["reduced"] == ["n_routed_experts"]
    assert (cfg["n_routed_experts"], cfg["published"]["n_routed_experts"]) \
        == (8, 64)
    prog = M.program_config(cfg)
    assert (prog.moe.n_experts, prog.moe.held, prog.moe.top_k) == (64, 8, 6)
    assert prog.mla.q_lora_rank is None and prog.moe.dropless
    assert A.mla_softmax_scale(prog) == pytest.approx(0.1147214, abs=5e-8)
    assert (prog.rope_scaling.factor, prog.rope_scaling.mscale_all_dim) \
        == (40, 0.707)
    bench = spec.load_benchmark()
    entry = {c["name"]: c for c in bench["configs"]}["deepseek-v2-lite-ep8"]
    assert entry["reduced"] == cfg["reduced"]


@pytest.mark.parametrize("key,value", [
    ("scoring_func", "sigmoid"), ("topk_method", "group_limited_greedy"),
    ("norm_topk_prob", True), ("routed_scaling_factor", 2.5),
    ("mscale", 1.0)])
def test_a_router_or_rope_the_program_does_not_serve_is_refused(key, value):
    cfg = _config()
    if key == "mscale":
        cfg["rope_scaling"] = dict(cfg["rope_scaling"], mscale=value)
    else:
        cfg[key] = value
    with pytest.raises(ValueError, match="the program serves"):
        M.program_config(cfg)


# ------------------------------------------------------------ the readers

DECODE = "jit_fused_decode_steps(8)"
PREFILL = "jit__chunk_prefill_fn(1)"


def _span(name, start, dur, **args):
    return PT.Span(name, start, dur, tuple(args.items()))


def test_expert_scopes_self_time_in_the_decode_program():
    """A decode program's ops under mlp/moe_route and mlp/moe_experts (one
    a loop around the other's fusion), one without metadata inheriting its
    operand's scope, and the same scopes in the chunk program, which do
    not count."""
    pt = PT.ProgramTrace(
        bench_quanta=[Event("perfbench.quantum.0", 0, 10_000_000)],
        spans=[_span("repro.quantum", 100_000, 9_800_000,
                     moe_decode_pairs=30, moe_decode_experts_hit=20)],
        modules=[Event(PREFILL, 1_000_000, 2_000_000),
                 Event(DECODE, 4_000_000, 4_000_000)],
        ops=[PT.Op("%fusion.1 = f(x)", 1_000_000, 500_000, "mlp"),
             PT.Op("%while.2 = w(y)", 4_000_000, 2_000_000, "mlp"),
             PT.Op("%fusion.3 = f(y)", 4_500_000, 1_000_000, "mlp"),
             PT.Op("%copy.4 = c(%fusion.3)", 6_000_000, 500_000,
                   PT.UNSCOPED),
             PT.Op("%fusion.5 = f(z)", 7_000_000, 500_000, "attn_decode")])
    names = {("1", "%fusion.1 = f(x)"): "jit(f)/mlp/moe_experts/dot:",
             ("8", "%while.2 = w(y)"): "jit(f)/mlp/moe_route/while:",
             ("8", "%fusion.3 = f(y)"): "jit(f)/mlp/moe_experts/dot:",
             ("8", "%fusion.5 = f(z)"): "jit(f)/attn_decode/dot:"}
    got = moe_readings.expert_self_s(pt, names)
    assert got == pytest.approx({"moe_route": 1e-3, "moe_experts": 1.5e-3})


def test_expert_readers_are_silent_without_a_trace():
    """A run without a trace (and a program without the expert layer)
    gives the readers nothing: None, not an error."""
    ctx = Context(cfg=_config(), run=None, setup_s=0.0, peak={})
    assert moe_readings.moe_decode_ms(ctx) is None
    assert moe_readings.moe_decode_roofline(ctx) is None
