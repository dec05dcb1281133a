"""Host spans of the serving engines, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation`` named ``repro.<phase>``. The
profiler is the switch and the store: outside a profiler trace a span
records nothing and costs about a microsecond; inside one it lands in the
trace beside the device ops, on the same clock. Spans are opened once per
scheduling quantum and phase, never per token; the spans of one request
are linked by the rids they carry. Arguments are attached only while a
trace is being taken.

One quantum of ``ServingEngine``, at any shard count::

    repro.quantum            step_num = the engine's quantum count
      repro.admit            rids admitted
      repro.prefill.prepare  pack the chunks, build token and mask arrays
      repro.prefill.launch   rids packed into the chunk launch
      repro.prefill.fetch    first tokens to the host
      repro.prefill.finish   rids finished: prefix index, metering, arming
      repro.decode.launch    the fused decode scan
      repro.decode.fetch     tokens and emission masks to the host
      repro.decode.emit      per-micro-step emission and metering
      repro.release          pages of finished slots back to the pool

``AsyncServingServer`` adds ``repro.server.pump`` (tokens into the client
streams) between quanta.
"""
from __future__ import annotations

from typing import Iterable

import jax

PREFIX = "repro."
_ANNOTATION = jax.profiler.TraceAnnotation


def enabled() -> bool:
    """Whether a profiler trace is being taken."""
    return _ANNOTATION.is_enabled()


def span(name: str) -> jax.profiler.TraceAnnotation:
    return _ANNOTATION(PREFIX + name)


def quantum(n: int) -> jax.profiler.StepTraceAnnotation:
    return jax.profiler.StepTraceAnnotation(PREFIX + "quantum", step_num=n)


def tag(open_span, name: str, rids: Iterable[int]) -> None:
    """Attach ``rids`` to an open span, space-separated (the profiler
    splits argument values at commas), while a trace is taken."""
    if enabled():
        open_span.set_metadata(**{name: " ".join(str(r) for r in rids)})
