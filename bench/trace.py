"""Record a profiler trace of the end of the window, and reduce it to
numbers: device busy time, idle gaps by the host span that covers them,
the device operations that took most time, and each kernel's share of its
roofline.

The traced part is the last ``trace_s`` seconds of the window. Its bounds
in the trace are those of the benchmark's own host spans (``bench.*``,
written as profiler annotations): from the start of the first to the end
of the last. Busy time is the union of the intervals of the device's
operation events in it, averaged over the chips used.
"""
from __future__ import annotations

import bisect
import re
import shutil
import time
from pathlib import Path

from bench import plugins, work

KERNELS = plugins.names("kernels")    # each counted by bench/kernels/<name>.py
SPANS = ("bench.step", "bench.submit", "bench.collect",
         "bench.generator_wait")


class Tracer:
    """Starts and stops the profiler from the replay loop's step hook."""

    def __init__(self, out_dir: Path, seconds: float, trace_s: float,
                 engines):
        self.dir = Path(out_dir)
        self.seconds, self.trace_s = seconds, trace_s
        self.engines = engines
        self.start_at = self.stop_at = None
        self.state = "idle"
        self.snap: list = []          # engine counters at start and stop

    def arm(self, t_window: float) -> None:
        self.stop_at = t_window + self.seconds
        self.start_at = self.stop_at - min(self.trace_s, self.seconds)

    def _snapshot(self) -> None:
        st = [e.stats() for e in self.engines]
        self.snap.append((time.perf_counter(),
                          sum(s.prefill_tokens for s in st)))

    def poll(self, t: float) -> None:
        import jax
        if self.start_at is None:
            return
        if self.state == "idle" and t >= self.start_at:
            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # no per-function host events
            opts.host_tracer_level = 1        # keeps the bench.* spans
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self._snapshot()
            self.state = "on"
        elif self.state == "on" and t >= self.stop_at:
            self.stop()

    def stop(self) -> None:
        import jax
        if self.state == "on":
            self._snapshot()
            jax.profiler.stop_trace()
            self.state = "done"

    def reduce(self, rec, cfg: dict, chips: int, kind: str):
        self.stop()
        if self.state != "done":
            return None
        path = sorted(self.dir.glob("**/*.xplane.pb"))[-1]
        red = reduce_file(path, cfg, chips, work.peaks(kind))
        if red is None:
            return None
        (t0, p0), (t1, p1) = self.snap
        red.tokens_processed = p1 - p0
        steps = [s for s in rec.steps if t0 <= s[1] <= t1]
        red.tokens_emitted = sum(s[2] for s in steps)
        red.tokens_processed += red.tokens_emitted - sum(s[3] for s in steps)
        return red


def _union(iv: list) -> list:
    out: list = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(iv: list, lo: float, hi: float) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in iv if b > lo and a < hi]


_SHAPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")


def shapes(text: str) -> list[tuple[str, tuple[int, ...]]]:
    """Every ``dtype[d0,d1,...]`` in an HLO instruction's text, in order."""
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(text)]


class Reduced:
    """What one trace says; the readers in bench/layer_metrics use it."""

    def __init__(self):
        self.window_s = 0.0
        self.busy_s = 0.0
        self.steps = 0
        self.tokens_processed = 0
        self.tokens_emitted = 0
        self.ops: dict = {}           # name -> device seconds
        self.gaps: dict = {}          # host span -> idle seconds
        self.kernels: dict = {}       # kernel -> [roofline s, device s]
        self.bounds: dict = {}        # kernel -> {"compute"|"memory": n}
        self.calls: dict = {}         # kernel -> calls in the window
        self.linear_dims: set = set() # (K, N) of the AWQ calls seen

    def roofline_share(self, kernel: str) -> float | None:
        roof, dur = self.kernels.get(kernel, (0.0, 0.0))
        return 100.0 * roof / dur if dur > 0 else None

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}


# ops whose events enclose the events of the ops they run
CONTAINERS = ("while", "conditional", "call")
_INSTR = re.compile(r"^%?([^\s=(]+)")


def op_name(event_name: str) -> str:
    """A stable name for a device operation. On the TPU an op's event is
    named by its HLO instruction (``%awq_matmul.61 = f32[...] ...``); the
    instruction's name without its numeric suffix is kept, so a kernel
    reads as its own name (``awq_matmul``)."""
    m = _INSTR.match(event_name)
    name = m.group(1) if m else event_name
    return re.sub(r"[._]\d+$", "", name)


def kernel_work(kernel: str, text: str, cfg: dict):
    """(FLOPs, bytes) of one kernel call from its instruction text, by the
    kernel's module ``bench/kernels/<kernel>.py``."""
    return plugins.kernel(kernel).work(text, cfg)


def reduce_file(path: Path, cfg: dict, chips: int, peak: dict) -> Reduced | None:
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    spans: list = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            devices.append(plane)
        for line in plane.lines:
            for e in line.events:
                if e.name in SPANS:
                    spans.append((e.name, e.start_ns, e.end_ns))
    if not spans or not devices:
        return None
    lo = min(s[1] for s in spans)
    hi = max(s[2] for s in spans)
    red = Reduced()
    red.window_s = (hi - lo) * 1e-9
    red.steps = sum(s[0] == "bench.step" for s in spans)
    busy_all = []
    names: dict = {}              # event name -> stable op name
    for plane in devices[:chips]:
        ivs = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                if e.end_ns <= lo or e.start_ns >= hi:
                    continue
                ivs.append((e.start_ns, e.end_ns))
                dur = e.duration_ns * 1e-9
                name = names.get(e.name)
                if name is None:
                    name = names[e.name] = op_name(e.name)
                if name not in CONTAINERS:
                    red.ops[name] = red.ops.get(name, 0.0) + dur
                if name in KERNELS:
                    text = e.name
                    fl, nb = kernel_work(name, text, cfg)
                    red.calls[name] = red.calls.get(name, 0) + 1
                    if name == "awq_matmul":
                        sh = shapes(text)
                        red.linear_dims.add((sh[1][1][1], sh[0][1][1]))
                    roof, bound = work.roofline_s(fl, nb, peak)
                    acc = red.kernels.setdefault(name, [0.0, 0.0])
                    acc[0] += roof
                    acc[1] += dur
                    bd = red.bounds.setdefault(name, {})
                    bd[bound] = bd.get(bound, 0) + 1
        busy = _clip(_union(ivs), lo, hi)
        busy_all.append(busy)
        red.busy_s += sum(b - a for a, b in busy) * 1e-9
    red.busy_s /= max(1, len(busy_all))
    # idle gaps of the first chip, named by the host span covering most
    # of each (the spans are disjoint: one host thread makes them)
    spans.sort(key=lambda x: x[1])
    starts = [x[1] for x in spans]
    busy = busy_all[0]
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        best, cover = "host.other", 0
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(spans) and spans[i][1] < b:
            c = min(b, spans[i][2]) - max(a, spans[i][1])
            if c > cover:
                best, cover = spans[i][0], c
            i += 1
        red.gaps[best] = red.gaps.get(best, 0.0) + (b - a) * 1e-9
    return red
