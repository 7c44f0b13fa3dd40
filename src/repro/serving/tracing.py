"""Host spans and counters of the serving step.

``span(stats, name)`` marks one phase of the host's work. It opens a
`jax.profiler.TraceAnnotation`, so that the phase sits in the profiler's
host plane on the device trace's clock whenever a trace is being recorded
(and costs a no-op check when none is), and adds the phase's wall time to
``stats``: one to its count, the seconds to its total and to its running
maximum, all keyed by ``name``. A body that raises records nothing. The
helper blocks on nothing: a span around an asynchronous dispatch times the
enqueue, and the wait for the device shows in the span that fetches the
result.

The serving step's spans, one level under the root:

  serve.step      one `Scheduler.step()` call
  serve.admit     placing queued or parked requests on slots: before the
                  dispatch, and the backfill after it
  serve.plan      drafts, pressure relief, packing the token block, and
                  the pager's extend / commit_chunk
  serve.upload    the page tables and the step's operands to the device
  serve.dispatch  the jitted call
  serve.wait      the fetch of the sampled tokens, which blocks until the
                  device has run the step
  serve.commit    stream events, finished slots, prefix registration and
                  rollbacks
  serve.restore   a parked request's pages back from the host tier
                  (inside serve.admit)
"""
from __future__ import annotations

import dataclasses
import time

import jax


@dataclasses.dataclass
class SpanStats:
    """Per span name: how often it ran, its total and its longest run."""
    span_count: dict[str, int] = dataclasses.field(default_factory=dict)
    span_s: dict[str, float] = dataclasses.field(default_factory=dict)
    span_max_s: dict[str, float] = dataclasses.field(default_factory=dict)

    def record(self, name: str, seconds: float) -> None:
        self.span_count[name] = self.span_count.get(name, 0) + 1
        self.span_s[name] = self.span_s.get(name, 0.0) + seconds
        self.span_max_s[name] = max(self.span_max_s.get(name, 0.0), seconds)


class span:
    """``with span(stats, name) as sp:`` times the body as span ``name``
    of ``stats``; ``sp.s`` holds the body's seconds once it has ended.
    (A class rather than a generator: it costs half as much.)"""
    __slots__ = ("_stats", "_name", "_note", "_t0", "s")

    def __init__(self, stats: SpanStats, name: str):
        self._stats, self._name = stats, name
        self._note = jax.profiler.TraceAnnotation(name)

    def __enter__(self) -> span:
        self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.s = time.perf_counter() - self._t0
        self._note.__exit__(exc_type, exc, tb)
        if exc_type is None:
            self._stats.record(self._name, self.s)


@dataclasses.dataclass(frozen=True)
class StepTimes:
    """The serving step's spans and counters since `reset_stats()`, as
    plain numbers (`EngineStats.step_times`)."""
    spans: dict[str, tuple[int, float, float]]  # name → (count, s, max s)
    kinds: dict[str, tuple[int, float]]  # "chunk" / "decode" → (steps, s)
    queue_wait_s: float       # Σ submit → admission to a slot
    queue_waits: int          # requests admitted from the queue
    prefill_s: float          # Σ admission → end of the first token's step
    prefills: int             # requests given their first token
    pool_pages_drawn: int     # Σ over dispatches of pages drawn from the pool
    pool_pages_usable: int    # pages the pool can hand out
    dispatches: int
