"""Chip smoke test: serve llama-paper-1b at full width on one TPU.

The production serving path, end to end, through the entry points a user
calls: ``AsyncServingServer`` over a ``ServingEngine`` with the paged KV
pool, chunked prefill and prefix sharing, running the Pallas attention
kernels compiled for the chip. Weights are random, made from ``--seed``.
The same requests are then served again on the jnp attention path
(``attn_impl="ref"``) in the same process, and the two runs are compared:
logits at the last prompt position within a stated bf16 tolerance, and
the first generated token of every request exactly.

    python chip_smoke.py [--seed 0]      # one chip
    python chip_smoke.py --four-chips    # fleet phase only, four chips

``--four-chips`` serves the same requests through a 4-shard
``ShardedServingEngine`` (one replica and page pool per chip) and compares
its tokens with the single-device engine on chip 0.

The script needs a TPU. With none, it exits non-zero and prints no result.
Its last line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax                                                   # noqa: E402
import numpy as np                                           # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P   # noqa: E402

from repro.configs import llama_paper                        # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_serving_mesh              # noqa: E402
from repro.models import Model, ModelConfig                  # noqa: E402
from repro.serving import (AsyncServingServer, EngineConfig,  # noqa: E402
                           Request, ServingEngine, ShardedServingEngine)

ENGINE = EngineConfig(paged=True, page_size=16, prefill_chunk=256,
                      prefix_sharing=True, max_batch=8, max_len=1024,
                      profile="tpu_v5e")
N_REQUESTS = 8
PROMPT_LENS = (64, 700)     # spread over several chunks and many pages
SHARED_PAGES = 5            # the sharer's whole prompt: 5 of the donor's pages
MAX_NEW_TOKENS = 32

# Logit tolerance, relative to the largest |logit| of the ref path. The two
# paths differ only inside attention: the kernels keep the softmax weights
# and the P.V accumulation in float32 and round once to bf16, while the jnp
# path rounds the weights to bf16 before P.V. A bf16 rounding is at most
# 2**-9 relative (8-bit significand), so the attention outputs of one layer
# differ by about 2**-8 of their scale. The bf16 residual stream keeps that
# difference, and 22 layers add such independent differences in quadrature:
# sqrt(22) * 2**-8 ~ 1.8% of the activations' scale, which the final norm
# and lm_head carry to the logits. The bound allows about 3x that estimate.
LOGIT_RTOL = 0.05


class CompileLog:
    """Compile work JAX reports through its monitoring events: seconds
    spent tracing, lowering and compiling, backend compiles (a load from
    the persistent cache counts as one), and persistent-cache hits and
    writes. Counts from its creation on."""
    _SPANS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event in self._SPANS:
            self.compile_s += secs
        if event == self._SPANS[-1]:
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1      # JAX counts a miss as it writes

    def snapshot(self) -> Tuple[float, int, int]:
        return self.compile_s, self.compiles, self.cache_hits


@dataclasses.dataclass
class Served:
    """One serving pass: per-request tokens, finish reasons and (single
    engine only) logits at the last prompt position, plus the pass's wall
    time and the compile work JAX reported inside it."""
    tokens: Dict[int, List[int]]
    reasons: Dict[int, str]
    logits: Dict[int, np.ndarray]
    wall_s: float
    compile_s: float
    compiles: int
    cache_hits: int
    stats: Dict[str, float]


def make_requests(seed: int, vocab: int, n: int = N_REQUESTS,
                  lens=PROMPT_LENS, page_size: int = ENGINE.page_size,
                  shared_pages: int = SHARED_PAGES,
                  max_new_tokens: int = MAX_NEW_TOKENS) -> List[Request]:
    """``n`` requests from ``seed``: ``n - 1`` prompts spread over ``lens``
    (request 0, the donor, is the longest), and a sharer (rid ``n - 1``)
    whose whole prompt is the donor's first ``shared_pages`` pages, so its
    admission adopts those pages and its recomputed last token runs
    copy-on-write."""
    rng = np.random.default_rng(seed)
    lo, hi = lens
    lengths = np.linspace(hi, lo, n - 1).astype(int)
    lengths[1:] -= rng.integers(0, page_size, n - 2)    # off page boundaries
    lengths = np.maximum(lengths, 1)
    reqs = [Request(rid=i, prompt=rng.integers(0, vocab, int(L)).tolist(),
                    max_new_tokens=max_new_tokens)
            for i, L in enumerate(lengths)]
    shared = shared_pages * page_size
    assert len(reqs[0].prompt) > shared, "donor must outlast the shared run"
    reqs.append(Request(rid=n - 1, prompt=reqs[0].prompt[:shared],
                        max_new_tokens=max_new_tokens))
    return reqs


async def _serve(engine, requests: List[Request]) -> Dict[int, List[int]]:
    """Stream every request through the async front door. The sharer (the
    last request) arrives once the donor (the first) has streamed a token,
    i.e. once the donor's prompt pages are in the prefix index."""
    server = AsyncServingServer(engine)
    first_token = {r.rid: asyncio.Event() for r in requests}

    async def consume(rid: int) -> List[int]:
        toks = []
        async for tok in server.stream(rid):
            toks.append(tok)
            first_token[rid].set()
        return toks

    *early, sharer = requests
    tasks = {}
    for r in early:
        await server.submit(dataclasses.replace(r))
        tasks[r.rid] = asyncio.create_task(consume(r.rid))
    await first_token[early[0].rid].wait()
    await server.submit(dataclasses.replace(sharer))
    tasks[sharer.rid] = asyncio.create_task(consume(sharer.rid))
    streamed = {rid: await t for rid, t in tasks.items()}
    await server.drain()
    for rid, toks in streamed.items():
        resp = await server.result(rid)
        assert toks == resp.tokens, f"request {rid}: stream != response"
    return streamed


def serve(engine, requests: List[Request], log: CompileLog) -> Served:
    """Serve ``requests`` once on ``engine`` and check every request ended
    by EOS or by its length budget."""
    gc.collect()                 # a previous pass's engine frees its pool
    record = isinstance(engine, ServingEngine)    # the fleet keeps none
    if record:
        engine.prompt_logits = {}
    s0, n0, h0 = log.snapshot()
    t0 = time.perf_counter()
    tokens = asyncio.run(_serve(engine, requests))
    wall = time.perf_counter() - t0
    s1, n1, h1 = log.snapshot()
    reasons = {rid: engine.responses[rid].finish_reason for rid in tokens}
    bad = {rid: why for rid, why in reasons.items()
           if why not in ("eos", "length")}
    assert not bad, f"requests ended badly: {bad}"
    logits = ({rid: np.asarray(jax.device_get(x), np.float32)
               for rid, x in engine.prompt_logits.items()} if record else {})
    return Served(tokens=tokens, reasons=reasons, logits=logits, wall_s=wall,
                  compile_s=s1 - s0, compiles=n1 - n0, cache_hits=h1 - h0,
                  stats=engine.stats())


def serving_phase(cfg: ModelConfig, attn_impl: str, params,
                  requests: List[Request], log: CompileLog,
                  engine_cfg: EngineConfig = ENGINE,
                  passes: int = 1) -> List[Served]:
    """Serve ``requests`` ``passes`` times on ``cfg`` with ``attn_impl``,
    each pass on a fresh engine. The first pass compiles; later passes
    reuse the compiled programs and time serving alone."""
    model = Model(dataclasses.replace(cfg, attn_impl=attn_impl))
    return [serve(ServingEngine(model, params, engine_cfg), requests, log)
            for _ in range(passes)]


def compare(kernel: Served, ref: Served) -> Dict[str, float]:
    """Kernel path vs ref path: worst logit error relative to the largest
    ref logit, first-token agreement, and how much of each later stream
    agrees (the length of the common prefix)."""
    rids = sorted(ref.tokens)
    err = max(float(np.abs(kernel.logits[r] - ref.logits[r]).max())
              for r in rids)
    scale = max(float(np.abs(ref.logits[r]).max()) for r in rids)
    first = sum(kernel.tokens[r][0] == ref.tokens[r][0] for r in rids)

    def common(a, b):
        n = 0
        while n < min(len(a), len(b)) and a[n] == b[n]:
            n += 1
        return n
    agree = sum(common(kernel.tokens[r], ref.tokens[r]) for r in rids)
    return {"logit_max_abs_err": err, "logit_max_abs_ref": scale,
            "logit_rel_err": err / scale, "first_token_agree": first,
            "requests": len(rids),
            "identical_streams": sum(kernel.tokens[r] == ref.tokens[r]
                                     for r in rids),
            "common_prefix_tokens": agree,
            "tokens": sum(len(ref.tokens[r]) for r in rids)}


def _device() -> Dict[str, object]:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (devices: {devs}); this script "
              "only runs on the chip", file=sys.stderr)
        sys.exit(1)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _init_params(cfg: ModelConfig, seed: int, log: CompileLog,
                 sharding=None):
    s0, _, h0 = log.snapshot()
    t0 = time.perf_counter()
    params = jax.jit(Model(cfg).init, out_shardings=sharding)(
        jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    s1, _, h1 = log.snapshot()
    print(f"params: {sum(x.size for x in jax.tree_util.tree_leaves(params))}"
          f" in {time.perf_counter() - t0:.2f} s (compile_s={s1 - s0:.2f}, "
          f"cache_hits={h1 - h0})")
    return params


def one_chip(seed: int, log: CompileLog) -> None:
    cfg = llama_paper.make("full", "llama-paper-1b")
    params = _init_params(cfg, seed, log)
    reqs = make_requests(seed, cfg.vocab)
    print("prompt lengths:", [len(r.prompt) for r in reqs])
    runs = {}
    for impl in ("pallas", "ref"):
        cold, warm = serving_phase(cfg, impl, params, reqs, log, passes=2)
        runs[impl] = warm
        n_tok = sum(len(t) for t in warm.tokens.values())
        print(f"[{impl}] compile_s={cold.compile_s:.2f} "
              f"(compiles={cold.compiles}, cache_hits={cold.cache_hits}) "
              f"first_pass_s={cold.wall_s:.2f} serve_s={warm.wall_s:.3f} "
              f"(compiles in window={warm.compiles}) tokens={n_tok} "
              f"prefix_hit_tokens={warm.stats['prefix_hit_tokens']} "
              f"reasons={sorted(set(warm.reasons.values()))}")
        assert warm.stats["prefix_hit_tokens"] > 0, "no prefix was adopted"
        assert cold.tokens == warm.tokens, f"[{impl}] passes disagree"
    cmp = compare(runs["pallas"], runs["ref"])
    print("pallas vs ref:", json.dumps(cmp))
    print(f"peak_bytes_in_use: "
          f"{jax.devices()[0].memory_stats()['peak_bytes_in_use']}")
    print(f"compile cache: hits={log.cache_hits} writes={log.cache_writes}")
    assert cmp["logit_rel_err"] <= LOGIT_RTOL, (
        f"logits differ by {cmp['logit_rel_err']:.4f} of their scale "
        f"(> {LOGIT_RTOL})")
    assert cmp["first_token_agree"] == cmp["requests"], \
        "first generated tokens differ between pallas and ref"


def four_chips(seed: int, log: CompileLog) -> None:
    devs = jax.devices()
    assert len(devs) == 4, f"--four-chips needs 4 devices, have {len(devs)}"
    cfg = llama_paper.make("full", "llama-paper-1b")
    model = Model(dataclasses.replace(cfg, attn_impl="pallas"))
    mesh = make_serving_mesh(4)
    # weights made replicated in place: one copy per chip, none staged on
    # chip 0 (the fleet's own device_put of them is then a no-op)
    params = _init_params(cfg, seed, log, NamedSharding(mesh, P()))
    reqs = make_requests(seed, cfg.vocab)
    engine = ShardedServingEngine(model, params,
                                  dataclasses.replace(ENGINE, shards=4), mesh)
    fleet = serve(engine, reqs, log)
    placed = list(engine.shard_requests)
    print(f"[fleet] first_pass_s={fleet.wall_s:.2f} (compile_s="
          f"{fleet.compile_s:.2f}) placements={placed}")
    for d in devs:
        st = d.memory_stats()
        print(f"device {d.id}: bytes_in_use={st['bytes_in_use']} "
              f"peak_bytes_in_use={st['peak_bytes_in_use']}")
    # the single-device reference runs on chip 0 once the fleet is gone
    del engine
    params = jax.device_put(params, devs[0])
    single = serve(ServingEngine(model, params, ENGINE), reqs, log)
    print(f"[single] first_pass_s={single.wall_s:.2f} (compile_s="
          f"{single.compile_s:.2f})")
    same = sum(fleet.tokens[r] == single.tokens[r] for r in single.tokens)
    print(f"fleet vs single-device: {same}/{len(single.tokens)} "
          "identical token streams")
    assert all(n > 0 for n in placed), f"a shard served nothing: {placed}"
    assert same == len(single.tokens), "fleet tokens differ from one chip"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-shard fleet phase and its "
                         "comparison with one chip")
    args = ap.parse_args()
    device = _device()
    print(f"device: {device['kind']} x{device['count']}")
    print(f"compile cache: {enable_compile_cache()}")
    log = CompileLog()
    if args.four_chips:
        four_chips(args.seed, log)
        print(f"compile cache: hits={log.cache_hits} "
              f"writes={log.cache_writes}")
    else:
        one_chip(args.seed, log)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
