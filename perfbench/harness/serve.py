"""Drive the program's served path: ``AsyncServingServer.submit`` /
``stream`` over a ``ServingEngine`` or a ``ShardedServingEngine``, with
the cell's traffic.

A run is: warm-up traffic that compiles every program variant the window
can use, then the cell's own traffic from ``t_traffic``; the window opens
``warmup_s`` later and lasts ``seconds``. Open-loop sessions start on
their schedule whatever the server does; closed-loop workers start their
next session when the last one ends. A session's next turn is sent its
think time after the previous answer's last token, and a history mix
resends every earlier turn with the answer served for it. No session or
turn is sent once its due time passes the window's end. Every token's arrival at its client is
stamped on the host clock.

The engine's ``step`` is wrapped by the benchmark (never edited): at the
quantum boundaries nearest the window's ends it snapshots the engine's
counters, and in a traced run it names each quantum for the profiler and
records the work the quantum did, read from the requests' public fields.
"""
from __future__ import annotations

import asyncio
import dataclasses
import sys
import time
from typing import Callable, Dict, List, Optional

import jax

from harness.trace import QUANTUM, SPAN_PREFIX
from harness.traffic import Session, Traffic
from repro.serving import AsyncServingServer, Request

# engine and server phases given host spans in a traced run (wrapped only
# where the program still has them)
PHASES = {"engine": ("_admit", "_prefill_quantum", "_decode_chunk"),
          "server": ("_pump",)}
# the profiler records the first TRACE_SECONDS of the window: enough
# quanta for the per-layer ratios, and a trace the run can read quickly
TRACE_SECONDS = 20.0
WARMUP_LIMIT_S = 900.0


@dataclasses.dataclass
class Record:
    rid: int
    session: int
    turn: int
    prompt: List[int]
    due: float
    request: Request
    sent: Optional[float] = None           # when submit() was called
    tokens: List[int] = dataclasses.field(default_factory=list)
    times: List[float] = dataclasses.field(default_factory=list)
    finish_reason: Optional[str] = None


@dataclasses.dataclass
class Quantum:
    """Work of one traced quantum: prompt positions computed by its chunk
    launch, and the positions of the live slots in each decode micro-step."""
    index: int
    prefill: List[tuple]                   # (start, stop) per request
    decode: List[List[int]]                # per micro-step: query positions


class Run:
    def __init__(self, engine, traffic: Traffic, seconds: float,
                 trace_dir: Optional[str] = None):
        self.engine = engine
        self.traffic = traffic
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.records: List[Record] = []
        self._live: Dict[int, Record] = {}
        self._rid = 0
        self.snap: Dict[str, Dict] = {}
        self.quanta: List[Quantum] = []
        self._q = 0
        self.t_traffic = self.t_w0 = self.t_w1 = 0.0
        self.sessions_started = 0
        self.queue_depth_end: Optional[int] = None
        self.on_window: Optional[Callable[[str], None]] = None
        self._wrap_step()

    # ---------------------------------------------------------- quanta
    def _wrap_step(self) -> None:
        step = self.engine.step

        def wrapped(*args, **kwargs):
            try:
                return self._step(step, *args, **kwargs)
            except BaseException as e:     # the server's task swallows it
                self.error = e
                raise

        self.engine.step = wrapped
        self.tracing = False
        self.error: Optional[BaseException] = None

    def _step(self, step, *args, **kwargs):
        now = time.perf_counter()
        for edge, t in (("w0", self.t_w0), ("w1", self.t_w1)):
            if t and now >= t and edge not in self.snap:
                self.snap[edge] = self._counters(now)
        if not self.tracing:
            return step(*args, **kwargs)
        live = list(self._live.values())
        before = [self._progress(r) for r in live]
        k = self._q
        self._q += 1
        with jax.profiler.TraceAnnotation(f"{QUANTUM}{k}"):
            out = step(*args, **kwargs)
        self.quanta.append(self._work(k, live, before))
        return out

    def _counters(self, now: float) -> Dict:
        st = self.engine.stats()
        return {"t": now, "decode_tokens": st["decode_tokens"],
                "steps": st["steps"],
                "prefix_hit_tokens": st.get("prefix_hit_tokens", 0),
                "queue_depth": st["queue_depth"],
                "shards": st.get("shards", 1)}

    def _progress(self, r: Record):
        resp = self.engine.responses.get(r.rid)
        return (r.request.prefill_pos, r.request.shared_prefix_tokens,
                0 if resp is None else len(resp.tokens))

    def _work(self, k: int, live: List[Record], before) -> Quantum:
        prefill, steps = [], []
        for r, (p0, _, n0) in zip(live, before):
            p1, shared, n1 = self._progress(r)
            start = max(p0, shared)
            if p1 > start:
                prefill.append((start, p1))
            first = max(n0, 1)             # token 0 comes from the prefill
            for j in range(first, n1):
                i = j - first
                while len(steps) <= i:
                    steps.append([])
                steps[i].append(len(r.prompt) + j - 1)
        return Quantum(index=k, prefill=prefill, decode=steps)

    def span_phases(self, server) -> None:
        """Host spans around the engine's and the server's phases."""
        def span(name, fn):
            def inner(*a, **kw):
                with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
                    return fn(*a, **kw)
            return inner
        for obj, names in ((self.engine, PHASES["engine"]),
                           (server, PHASES["server"])):
            for name in names:
                fn = getattr(obj, name, None)
                if fn is not None:
                    setattr(obj, name, span(name.strip("_"), fn))

    # ---------------------------------------------------------- clients
    def _request(self, session: Session, t: int, prompt: List[int],
                 due: float) -> Record:
        rid = self._rid
        self._rid += 1
        req = Request(rid=rid, prompt=prompt,
                      max_new_tokens=session.turns[t].output)
        rec = Record(rid=rid, session=session.index, turn=t, prompt=prompt,
                     due=due, request=req)
        self.records.append(rec)
        return rec

    async def _send(self, server, rec: Record) -> None:
        self._live[rec.rid] = rec
        rec.sent = time.perf_counter()
        await server.submit(rec.request)
        async for tok in server.stream(rec.rid):
            rec.times.append(time.perf_counter())
            rec.tokens.append(tok)
        rec.finish_reason = self.engine.responses[rec.rid].finish_reason
        self._live.pop(rec.rid, None)

    async def _session(self, server, session: Session, due: float) -> None:
        answers: List[List[int]] = []
        for t, turn in enumerate(session.turns):
            if t:                      # the next turn follows the answer
                due = time.perf_counter() + turn.think_s
            if due >= self.t_w1:
                return
            await _sleep_until(due)
            rec = self._request(session, t,
                                self.traffic.prompt(session, t, answers), due)
            await self._send(server, rec)
            if rec.finish_reason not in ("length", "eos"):
                return
            answers.append(rec.tokens)

    async def _open_loop(self, server, tasks: List) -> None:
        i = 0
        while True:
            due = self.t_traffic + self.traffic.start_s(i)
            if due >= self.t_w1:
                return
            await _sleep_until(due)
            tasks.append(asyncio.create_task(
                self._session(server, self.traffic.session(i), due)))
            self.sessions_started = i = i + 1

    async def _worker(self, server, next_session: Callable[[], int]) -> None:
        while time.perf_counter() < self.t_w1:
            await self._session(server, self.traffic.session(next_session()),
                                time.perf_counter())

    # ------------------------------------------------------------ a run
    async def _until(self, t: float) -> None:
        """Sleep until ``t``, raising what the engine raised meanwhile."""
        while True:
            if self.error is not None:
                raise RuntimeError("the engine failed a quantum") \
                    from self.error
            left = t - time.perf_counter()
            if left <= 0:
                return
            await asyncio.sleep(min(left, 0.1))

    async def _guard(self, coro, limit_s: float, what: str) -> None:
        task = asyncio.create_task(coro)
        end = time.perf_counter() + limit_s
        while not task.done():
            if self.error is not None or time.perf_counter() > end:
                task.cancel()
                await asyncio.gather(task, return_exceptions=True)
                if self.error is not None:
                    raise RuntimeError(f"the engine failed during {what}") \
                        from self.error
                raise TimeoutError(f"{what} took over {limit_s:.0f} s")
            await asyncio.sleep(0.05)
        task.result()

    async def serve(self, warmup: Callable, warmup_s: float, drain_s: float,
                    workers: int) -> None:
        server = AsyncServingServer(self.engine)
        log("compile warm-up")
        await self._guard(warmup(self, server), WARMUP_LIMIT_S,
                          "the compile warm-up")
        log("traffic starts")
        self.t_traffic = time.perf_counter()
        self.t_w0 = self.t_traffic + warmup_s
        self.t_w1 = self.t_w0 + self.seconds
        tasks: List = []
        if self.traffic.loop == "open":
            tasks.append(asyncio.create_task(self._open_loop(server, tasks)))
        else:
            counter = iter(range(10 ** 9))
            tasks.extend(asyncio.create_task(
                self._worker(server, lambda: next(counter)))
                for _ in range(workers))
        await self._until(self.t_w0)
        log("window opens")
        self._edge("w0")
        if self.trace_dir:
            self.span_phases(server)
            self.tracing = True
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0       # spans and device ops only
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            await self._until(min(self.t_w1, self.t_w0 + TRACE_SECONDS))
            self.tracing = False
            jax.profiler.stop_trace()
        await self._until(self.t_w1)
        self._edge("w1")
        log("window closes")
        await self._drain(drain_s)
        self.queue_depth_end = len(self.engine.queue)
        for edge in ("w0", "w1"):            # no quantum ran after the edge
            self.snap.setdefault(edge, self._counters(time.perf_counter()))
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        # the server's drive task is cancelled when the event loop closes;
        # the quantum it is running finishes first

    def _edge(self, edge: str) -> None:
        if self.on_window is not None:
            self.on_window(edge)

    async def _drain(self, drain_s: float) -> None:
        """Open loop: wait, up to ``drain_s``, for the first token of every
        request due in the window."""
        end = time.perf_counter() + drain_s
        while time.perf_counter() < end and self.error is None:
            waiting = [r for r in self.records
                       if self.t_w0 <= r.due < self.t_w1 and not r.times
                       and r.finish_reason is None]
            if not waiting:
                return
            await asyncio.sleep(0.05)


async def _sleep_until(t: float) -> None:
    delay = t - time.perf_counter()
    if delay > 0:
        await asyncio.sleep(delay)


def log(msg: str) -> None:
    """A progress line on standard error, so a run that stalls or dies
    shows where."""
    print(f"[perfbench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


async def compile_warmup(run: Run, server) -> None:
    """Serve tiny requests that make the program compile every variant the
    window can launch: the fused decode scan at each step count up to
    ``sync_every`` (a lone request with budget k), admission of every
    number of requests at once up to ``max_batch`` per shard (a fleet
    spreads them over its shards, so every number up to all its slots),
    and, with prefix sharing, an adopted prefix (partial and
    whole-prompt, which copies on write)."""
    eng = run.engine
    cfg = eng.cfg
    vocab = eng.model.cfg.vocab

    async def serve(prompts, outs):
        recs = []
        for p, n in zip(prompts, outs):
            rid = run._rid
            run._rid += 1
            recs.append(asyncio.create_task(_served(server, Request(
                rid=rid, prompt=p, max_new_tokens=n))))
        return await asyncio.gather(*recs)

    for k in range(1, cfg.sync_every + 1):
        await serve([[k % vocab]], [k + 1])
    for m in range(1, cfg.max_batch * cfg.shards + 1):
        await serve([[(m + i) % vocab] for i in range(m)], [1] * m)
    if cfg.prefix_sharing:
        ps = cfg.page_size
        donor = [(7 * i + 3) % vocab for i in range(2 * ps + 1)]
        first = asyncio.Event()
        rid = run._rid
        run._rid += 1
        task = asyncio.create_task(_served(server, Request(
            rid=rid, prompt=donor, max_new_tokens=4 * cfg.sync_every),
            first))
        await first.wait()
        await serve([donor[:2 * ps], donor[:ps] + [1, 2, 3]], [2, 2])
        await task


async def _served(server, req: Request, first: Optional[asyncio.Event] = None):
    await server.submit(req)
    n = 0
    async for _ in server.stream(req.rid):
        n += 1
        if first is not None:
            first.set()
    return n
