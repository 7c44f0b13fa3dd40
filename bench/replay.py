"""Drive a server through its public API (``submit``/``step``/``collect``)
with a generated request list, and record what a client sees.

Open loop: each request is submitted once it is due, and its first token
is timed from its due time, so a stall is charged to every request it
delays. Closed loop: ``clients`` callers each keep one request in flight
and are timed from submission. Tokens are stamped when the ``step`` that
produced them returns, and kept per request as they stream, so that the
tokens of a request still in flight can be checked too.

A ramp of the same traffic (``ramp_s`` of the mix) runs first, so that
the window opens on a loaded server; then the window is ``seconds`` long.
Requests due (open) or submitted (closed) in it are the attempted ones. After it closes the loop goes on, with the
open loop's schedule still running and no new closed-loop requests, until
each attempted request has its first token, or ``grace_s`` has passed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

clock = time.perf_counter


@dataclasses.dataclass
class Sent:
    req: object                   # loadgen.Request
    t_ref: float                  # due (open) or submit (closed) time
    in_window: bool
    t_first: float | None = None
    t_last: float | None = None
    served: list = dataclasses.field(default_factory=list)  # streamed
    output: np.ndarray | None = None                        # collected


@dataclasses.dataclass
class Record:
    t0: float = 0.0
    t1: float = 0.0
    sent: dict = dataclasses.field(default_factory=dict)   # rid -> Sent
    gaps: list = dataclasses.field(default_factory=list)   # ITL, s
    tokens_in_window: int = 0
    steps_in_window: int = 0
    late: list = dataclasses.field(default_factory=list)   # submit - due, s
    spans: list = dataclasses.field(default_factory=list)  # (name, t0, t1)
    # per step: (start, end, tokens emitted, of them first tokens)
    steps: list = dataclasses.field(default_factory=list)


class Spans:
    """Host spans around the calls into the server: kept in memory, and
    written into the profiler's trace too while one is recorded."""

    def __init__(self, record: Record, annotate: bool):
        self.record = record
        self.annotate = annotate

    @contextlib.contextmanager
    def __call__(self, name: str):
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation(name):
                t = clock()
                yield
        else:
            t = clock()
            yield
        self.record.spans.append((name, t, clock()))


def run(server, reqs, mix: dict, seconds: float, *, grace_s: float = 60.0,
        on_window_start=None, on_window_end=None, on_step=None,
        span_names: bool = False) -> Record:
    """Drive ``reqs`` through ``server``: ``mix["ramp_s"]`` of the same
    traffic first (not measured), then the window of ``seconds``.
    ``on_window_start(t)`` and ``on_window_end(t)`` run as the window opens
    and closes, ``on_step(t)`` after every step."""
    rec = Record()
    span = Spans(rec, span_names)
    closed = mix["loop"] == "closed"
    queue = iter(reqs)
    in_flight = 0                 # closed loop: requests not yet finished
    pending = 0                   # attempted requests with no token yet
    nxt = None if closed else next(queue, None)
    opened = closed_ = False
    rec.t0 = t0 = clock() + mix["ramp_s"]
    end = t0 + seconds

    def submit(r, t_ref, now):
        nonlocal pending
        with span("bench.submit"):
            rid = server.submit(r.tokens, r.max_new, prefix_id=r.prefix_id)
        attempted = t0 <= t_ref < end
        rec.sent[rid] = Sent(r, t_ref, attempted)
        pending += attempted
        if attempted:
            rec.late.append(now - t_ref)

    while True:
        now = clock()
        if not opened and now >= t0:
            opened = True
            if on_window_start is not None:
                on_window_start(now)
        if not closed_ and now >= end:
            closed_ = True
            if on_window_end is not None:
                on_window_end(now)
        if closed:
            while now < end and in_flight < mix["clients"]:
                r = next(queue, None)
                if r is None:
                    raise RuntimeError("the closed loop ran out of requests")
                submit(r, now, now)
                in_flight += 1
        else:
            while nxt is not None and t0 + nxt.due_s <= now:
                if t0 + nxt.due_s >= end and not pending:
                    nxt = None
                    break
                submit(nxt, t0 + nxt.due_s, now)
                nxt = next(queue, None)
        if (now >= end and not pending) or now >= end + grace_s:
            break
        if server.idle:
            if closed or nxt is None:
                break
            with span("bench.generator_wait"):
                time.sleep(max(0.0, t0 + nxt.due_s - clock()))
            continue
        ts = clock()
        with span("bench.step"):
            events = server.step()
        t = clock()
        n_first = 0
        counted = t0 <= t < end
        rec.steps_in_window += counted
        for rid, tok in events:
            s = rec.sent[rid]
            s.served.append(int(tok))
            if s.t_first is None:
                s.t_first = t
                pending -= s.in_window
                n_first += 1
            elif counted:
                rec.gaps.append(t - s.t_last)
            rec.tokens_in_window += counted
            s.t_last = t
        rec.steps.append((ts, t, len(events), n_first))
        if on_step is not None:
            on_step(t)
        with span("bench.collect"):
            done = server.collect()
        for rid, out in done.items():
            rec.sent[rid].output = out
            in_flight -= 1
    rec.t1 = clock()
    return rec


def summary(rec: Record, seconds: float) -> dict:
    """The client-side numbers of one window."""
    att = [s for s in rec.sent.values() if s.in_window]
    ttft = [s.t_first - s.t_ref for s in att if s.t_first is not None]
    q = (lambda xs, p: float(np.percentile(xs, p)) if len(xs) else
         float("nan"))
    return {
        "attempted": len(att),
        "failed": sum(s.t_first is None for s in att),
        "tokens_per_s": rec.tokens_in_window / seconds,
        "ttft_p95_ms": 1e3 * q(ttft, 95),
        "itl_p50_ms": 1e3 * q(rec.gaps, 50),
        "itl_p95_ms": 1e3 * q(rec.gaps, 95),
        "ttft_n": len(ttft),
        "itl_n": len(rec.gaps),
        "late_p95_ms": 1e3 * q(rec.late, 95),
    }
