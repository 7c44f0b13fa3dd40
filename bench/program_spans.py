"""The program's own view of its serving step, for the per-layer readers.

Two sources, both of which a program without them leaves empty:

- ``step_times(ctx)``: each engine's `EngineStats.step_times` (host spans
  and step counters over the window), as the run read them at its close.
- ``read_trace()``: the program's ``serve.*`` host spans in the run's
  trace, which `bench/trace.py`'s `Tracer` leaves under
  ``<checkout>/.bench_trace``. They are on the device trace's clock, so
  the device-idle time of the traced part can be split by the host phase
  it lies in. The traced part is bounded as in `bench/trace.py`: from the
  first to the last of the benchmark's ``bench.*`` spans.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

from bench import trace

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".bench_trace"
STEP = "serve.step"       # the root span: one `Scheduler.step()` call
WAIT = "serve.wait"       # the host blocked on the device's result


def step_times(ctx) -> list:
    """The engines' `StepTimes`; empty where the program keeps none."""
    out = [getattr(s, "step_times", None) for s in ctx["stats"]]
    return [t for t in out if t is not None]


def step_kind_ms(ctx, kind: str) -> float | None:
    """Mean wall time of the engine steps of ``kind`` ("chunk": the block
    carried a prompt chunk; "decode": only decode rows), in ms."""
    k = [t.kinds.get(kind, (0, 0.0)) for t in step_times(ctx)]
    n = sum(c for c, _ in k)
    return 1e3 * sum(s for _, s in k) / n if n else None


@dataclasses.dataclass
class Phases:
    steps: int = 0            # bench.step spans in the traced part
    serve_steps: int = 0      # serve.step spans in it
    idle_s: float = 0.0       # device-idle seconds in it (first chip)
    phase_idle_s: dict = dataclasses.field(default_factory=dict)
    #   serve.* span name → device-idle seconds inside its spans
    host_idle_s: float = 0.0  # idle inside any host phase: a serve.* span
    #                           other than serve.step and serve.wait
    phase_s: dict = dataclasses.field(default_factory=dict)
    #   serve.* span name → its total seconds in the traced part


def _overlap_ns(a: list, b: list) -> int:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        tot += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def read_file(path: Path) -> Phases | None:
    """Split the idle time of one trace file by program phase; None when
    the trace holds no ``serve.*`` span or no device."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    bench, serve, device = [], {}, None
    for plane in pd.planes:
        if (device is None and plane.name.startswith("/device:TPU:")
                and "Core" not in plane.name):
            device = plane
        for line in plane.lines:
            for e in line.events:
                if e.name in trace.SPANS:
                    bench.append((e.name, e.start_ns, e.end_ns))
                elif e.name.startswith("serve."):
                    serve.setdefault(e.name, []).append(
                        (e.start_ns, e.end_ns))
    if not bench or not serve or device is None:
        return None
    lo = min(s[1] for s in bench)
    hi = max(s[2] for s in bench)
    ops = [(e.start_ns, e.end_ns) for line in device.lines
           if line.name == "XLA Ops" for e in line.events]
    busy = trace._clip(trace._union(ops), lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    idle = [[a, b] for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    out = Phases(steps=sum(s[0] == "bench.step" for s in bench),
                 idle_s=sum(b - a for a, b in idle) * 1e-9)
    host: list = []
    for name, ivs in serve.items():
        ivs = trace._clip(trace._union(ivs), lo, hi)
        out.phase_idle_s[name] = _overlap_ns(idle, ivs) * 1e-9
        out.phase_s[name] = sum(b - a for a, b in ivs) * 1e-9
        if name not in (STEP, WAIT):
            host += ivs
    out.serve_steps = sum(lo <= a and b <= hi
                          for a, b in serve.get(STEP, []))
    out.host_idle_s = _overlap_ns(idle, trace._union(host)) * 1e-9
    return out


def read_trace() -> Phases | None:
    """`read_file` of the newest trace file under `TRACE_DIR`."""
    files = sorted(TRACE_DIR.glob("**/*.xplane.pb"))
    return read_file(files[-1]) if files else None
