"""The serving engine's own instrumentation: host spans on the profiler's
clock, model scopes in the served programs' metadata, and the pool's
reserved-against-mapped page counter.

Spans are read back from a real profiler trace of a tiny paged, chunked,
prefix-sharing engine behind ``AsyncServingServer``; scopes from the
lowered programs' debug locations; the counter's host mirror is checked
against the device allocator after every quantum (fetched here only — the
engine never syncs for it), through copy-on-write and a preemption pin.
"""
import asyncio
import glob
import re

import jax
import numpy as np
import pytest

from repro.models import Model, ModelConfig
from repro.models.config import repeat_pattern
from repro.serving import (AsyncServingServer, EngineConfig, Request,
                           ServingEngine, ShardedServingEngine)
from repro.serving import engine as E
from repro.serving import sampling, trace

PS = 4
CH = 8
PHASES = ("admit", "prefill.prepare", "prefill.launch", "prefill.fetch",
          "prefill.finish", "decode.launch", "decode.fetch", "decode.emit",
          "release")


@pytest.fixture(scope="module")
def parts():
    cfg = ModelConfig(
        name="tiny-trace", family="dense", n_layers=2, d_model=64,
        n_heads=8, n_kv_heads=2, d_ff=128, vocab=256, dtype="float32",
        block_pattern=repeat_pattern(("dense",), 2), vocab_pad_multiple=8)
    m = Model(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def _engine(m, params, cls=ServingEngine, **kw):
    args = dict(max_batch=2, max_len=64, sync_every=4, paged=True,
                page_size=PS, prefill_chunk=CH, prefix_sharing=True)
    args.update(kw)
    return cls(m, params, EngineConfig(**args))


# ------------------------------------------------------------------ spans

def _host_spans(logdir):
    """The program's spans, in time order (the server runs quanta on its
    executor's threads, one trace line each)."""
    path = sorted(glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb"))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    return sorted(
        ((e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
         for plane in data.planes if plane.name.startswith("/host:")
         for line in plane.lines for e in line.events
         if e.name.startswith(trace.PREFIX)), key=lambda s: s[1])


@pytest.fixture(scope="module")
def traced(parts, tmp_path_factory):
    """Serve five requests (two share a whole prompt, so one adopts and
    copies on write) through the async server under the profiler."""
    m, params = parts
    eng = _engine(m, params)
    server = AsyncServingServer(eng)
    rng = np.random.default_rng(5)
    shared = list(rng.integers(0, 256, 3 * PS))
    prompts = [shared, list(rng.integers(0, 256, 13)), list(shared),
               list(rng.integers(0, 256, 6)), shared[:2 * PS] + [9, 8]]

    logdir = str(tmp_path_factory.mktemp("trace"))

    async def go():
        for rid, p in enumerate(prompts):
            await server.submit(Request(rid=rid, prompt=p,
                                        max_new_tokens=24 if rid else 40))
            while not eng.responses[0].tokens:
                # the donor registers its pages before the others come
                await asyncio.sleep(0.01)
        await server.drain()

    with jax.profiler.trace(logdir):
        asyncio.run(go())
    return eng, _host_spans(logdir), len(prompts)


def test_every_span_appears(traced):
    _, spans, _ = traced
    names = {n for n, *_ in spans}
    want = {trace.PREFIX + p for p in PHASES + ("quantum", "server.pump")}
    assert want <= names, sorted(want - names)


def test_phase_spans_nest_inside_a_quantum(traced):
    eng, spans, _ = traced
    quanta = [(s, e, a) for n, s, e, a in spans
              if n == trace.PREFIX + "quantum"]
    assert [a["step_num"] for _, _, a in quanta] == \
        list(range(1, eng._quantum + 1))
    for name, s, e, _ in spans:
        if name[len(trace.PREFIX):] in PHASES:
            assert any(qs <= s and e <= qe for qs, qe, _ in quanta), name


def test_rids_ride_as_arguments(traced):
    eng, spans, n = traced

    def rids(phase):
        out = []
        for name, _, _, args in spans:
            if name == trace.PREFIX + phase and "rids" in args:
                out += [int(r) for r in str(args["rids"]).split()]
        return out
    assert sorted(rids("admit")) == list(range(n))
    assert sorted(rids("prefill.finish")) == list(range(n))
    assert set(rids("prefill.launch")) == set(range(n))
    assert eng.prefix_hit_tokens > 0           # the sharing path was traced


def test_quanta_carry_the_page_counts(traced):
    eng, spans, _ = traced
    args = [a for n, _, _, a in spans if n == trace.PREFIX + "quantum"]
    st = eng.stats()
    assert sum(a["reserved_pages"] for a in args) == \
        st["reserved_page_quanta"]
    assert sum(a["mapped_pages"] for a in args) == st["mapped_page_quanta"]
    assert 0 < st["mapped_page_quanta"] <= st["reserved_page_quanta"]


def test_fleet_engine_opens_the_same_spans(parts, tmp_path):
    """``ShardedServingEngine`` (one shard here) carries the same span
    vocabulary, quantum numbering and rid arguments."""
    m, params = parts
    eng = ShardedServingEngine(m, params, EngineConfig(
        max_batch=2, max_len=64, sync_every=4, paged=True, page_size=PS,
        prefill_chunk=CH, shards=1, prefix_sharing=True))
    for rid in range(3):
        eng.submit(Request(rid=rid, prompt=[rid + 1] * 9, max_new_tokens=5))
    with jax.profiler.trace(str(tmp_path)):
        eng.run()
    spans = _host_spans(str(tmp_path))
    names = {n[len(trace.PREFIX):] for n, *_ in spans}
    assert names == set(PHASES) | {"quantum"}
    steps = [a["step_num"] for n, _, _, a in spans
             if n == trace.PREFIX + "quantum"]
    assert steps == list(range(1, eng._quantum + 1))
    admitted = [int(r) for n, _, _, a in spans
                if n == trace.PREFIX + "admit" and "rids" in a
                for r in str(a["rids"]).split()]
    assert sorted(admitted) == [0, 1, 2]


def test_untraced_spans_carry_nothing():
    assert not trace.enabled()
    with trace.span("admit") as sp:
        trace.tag(sp, "rids", iter(()))        # a no-op outside a trace


# ----------------------------------------------------------------- scopes

def test_model_scopes_name_the_served_programs(parts):
    m, params = parts
    eng = _engine(m, params)
    kw = dict(temperature=0.0, page_size=PS)
    fused = E._FUSED_STEPS.lower(
        m, params, eng.caches, eng.cur_tokens, eng.state,
        jax.random.PRNGKey(0), n_steps=2, freeze_inactive=True, **kw)
    tokens = np.zeros((1, CH), np.int32)
    chunk = E._CHUNK_PREFILL.lower(
        m, params, eng.caches, tokens, tokens, np.zeros((1,), np.int32),
        jax.random.PRNGKey(0), vocab=m.cfg.vocab, sharing=True, **kw)
    want = {"fused": ("attn_decode", "attn_proj", "mlp", "lm_head",
                      "sample"),
            "chunk": ("attn_prefill", "attn_proj", "mlp", "lm_head",
                      "sample")}

    def scoped(text, scope):       # an entry of a location's name stack
        return re.search(rf'["/]{scope}["/]', text) is not None
    for name, lowered in (("fused", fused), ("chunk", chunk)):
        text = lowered.as_text(debug_info=True)
        for scope in want[name]:
            assert scoped(text, scope), (name, scope)
    fused_text = fused.as_text(debug_info=True)
    assert not scoped(fused_text, "attn_prefill")
    assert sampling.fused_decode_steps.__name__ in fused_text


# ---------------------------------------------------------------- counter

class CountedEngine(ServingEngine):
    """After every quantum: the host-mirrored mapped count equals the
    device allocator's (``num_pages`` - free-stack top), and never exceeds
    the pages reserved."""

    def step(self, max_steps=10_000):
        ran = super().step(max_steps)
        top = int(jax.device_get(self.caches["paged"]["top"]))
        assert self.mapped_pages() == self.num_pages - top, self._quantum
        assert self.mapped_pages() <= self.num_pages - self.free_pages[0]
        self.checked = getattr(self, "checked", 0) + 1
        return ran


def _serve_staggered(eng, batches, gap):
    """Submit each batch of requests ``gap`` quanta after the last, then
    drain."""
    for batch in batches:
        for r in batch:
            eng.submit(Request(**r))
        for _ in range(gap):
            eng.step()
    eng.run()
    return eng


RNG = np.random.default_rng(21)


def _prompt(n):
    return list(RNG.integers(0, 256, n))


@pytest.mark.parametrize("case", ["cow", "preempt", "unshared",
                                  "monolithic"])
def test_mapped_pages_mirror_the_device(parts, case):
    m, params = parts
    if case == "cow":
        # a whole-prompt twin copies its tail page on write; a third
        # request adopts two pages and writes its own tail
        shared = _prompt(3 * PS)
        eng = _serve_staggered(_engine(m, params, CountedEngine,
                                       max_batch=3), [
            [dict(rid=0, prompt=shared, max_new_tokens=40)],
            [dict(rid=1, prompt=list(shared), max_new_tokens=5),
             dict(rid=2, prompt=shared[:2 * PS] + [1, 2, 3],
                  max_new_tokens=5)]], gap=4)
        assert eng.prefix_hit_tokens > 0
    elif case == "preempt":
        # two decodes fill both slots; a higher class evicts one, whose
        # indexed prompt pages stay pinned until it resumes
        eng = _engine(m, params, CountedEngine, preemption=True)
        eng = _serve_staggered(eng, [
            [dict(rid=0, prompt=_prompt(12), max_new_tokens=24),
             dict(rid=1, prompt=_prompt(12), max_new_tokens=24)],
            [dict(rid=2, prompt=_prompt(4), max_new_tokens=4,
                  priority=1)]], gap=6)
        assert eng.preemption_count >= 1
    elif case == "unshared":
        eng = _serve_staggered(
            _engine(m, params, CountedEngine, prefix_sharing=False),
            [[dict(rid=i, prompt=_prompt(5 + 7 * i), max_new_tokens=7)
              for i in range(3)]], gap=1)
    else:
        eng = _serve_staggered(
            _engine(m, params, CountedEngine, prefix_sharing=False,
                    prefill_chunk=None),
            [[dict(rid=i, prompt=_prompt(5 + 7 * i), max_new_tokens=7)
              for i in range(3)]], gap=1)
    st = eng.stats()
    assert eng.checked == eng._quantum
    assert 0 < st["mapped_page_quanta"] <= st["reserved_page_quanta"]
    assert eng.mapped_pages() == 0 and eng.free_pages == [eng.num_pages]
