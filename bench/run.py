"""Run one benchmark cell once, on the chips of the machine it starts on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json`` at the root of the
checkout; its configuration, traffic mix, limit and per-layer metric
readers, and the architecture module and plain reference that its
configuration names, are files under ``bench/`` found by name
(``bench/plugins.py``). In order, a run

1. makes the weights on the device from ``--seed``;
2. builds the program's engine (or a Router over one-chip replicas) and
   compiles every step variant the serving path can dispatch;
3. drives the mix's ramp, resets the engine's counters, and drives the
   window of ``--seconds`` through ``submit``/``step``/``collect``
   (``--trace 1`` records a profiler trace of its last 10 s);
4. reads the peak device memory, frees the server, and compares a sample
   of the served tokens with the plain reference (``bench/check.py``);
5. prints each compared number beside its limit on standard error, and
   one JSON line on standard output: the end-to-end metrics
   (``--trace 0``) or the per-layer metrics (``--trace 1``).

It exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for, or when the cell or a file it names is
missing.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_S = 10.0       # length of the traced part, at the end of the window
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import plugins  # noqa: E402
from bench.plugins import CellError  # noqa: E402


def load_cell(name: str) -> dict:
    """The cell's entry of BENCHMARK.json with its configuration, mix,
    limit and metric list resolved from their files."""
    spec_path = ROOT / "BENCHMARK.json"
    if not spec_path.is_file():
        raise CellError(f"no BENCHMARK.json at {ROOT}")
    spec = json.loads(spec_path.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    files = {"config": ROOT / configs[cell["config"]]["file"],
             "mix": BENCH / "traffic" / f"{cell['traffic']}.json",
             "limit": BENCH / "limits" / f"{name}.json"}
    for what, path in files.items():
        if not path.is_file():
            raise CellError(f"{what} file {path} is missing")
    out = dict(cell)
    for what, path in files.items():
        out[what] = json.loads(path.read_text())
    plugins.arch(out["config"])
    plugins.reference(out["config"])
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    out["end_to_end"] = e2e
    out["per_layer"] = [m for m in spec["per_layer"]
                        if name in m.get("workloads", [name])
                        and m["moves"] in moved]
    return out


def reader(metric: str):
    path = BENCH / "layer_metrics" / f"{metric}.py"
    if not path.is_file():
        raise CellError(f"no reader {path} for metric {metric!r}")
    spec = importlib.util.spec_from_file_location(f"_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def enable_cache() -> str:
    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise CellError(f"the cell needs {chips} TPU chip(s); JAX found "
                        f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def peak_bytes(chips: int) -> int | None:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        ms = d.memory_stats() or {}
        if "peak_bytes_in_use" in ms:
            peaks.append(int(ms["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts traces and backend compiles while armed."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if self.armed and name in self.EVENTS:
            self.count += 1


def serve(cell: dict, args, counter: CompileCounter, fault=None):
    """Set up, drive the ramp and the window; return host data only, so
    that every device buffer of the server is freed when this returns."""
    import jax

    from bench import loadgen, replay, sut, trace, weights
    cfg, mix = cell["config"], cell["mix"]
    w = weights.make_weights(cfg, args.seed)
    server, engines = sut.build(cfg, w)
    if fault is not None:
        fault(server, engines)
    server.warmup()
    reqs = loadgen.generate(mix, cfg["vocab_size"], args.seed, args.seconds)
    jax.block_until_ready(w)
    setup_s = time.perf_counter() - T_START
    tracer = trace.Tracer(ROOT / ".bench_trace", args.seconds, TRACE_S,
                          engines) if args.trace else None
    stats = []

    def window_start(t):
        server.reset_stats()
        counter.armed = True
        if tracer is not None:
            tracer.arm(t)

    def window_end(_t):
        counter.armed = False
        stats.extend(e.stats() for e in engines)

    rec = replay.run(server, reqs, mix, args.seconds,
                     on_window_start=window_start, on_window_end=window_end,
                     on_step=tracer.poll if tracer else None,
                     span_names=bool(args.trace))
    counter.armed = False
    memory = peak_bytes(cell["chips"])
    kind = jax.devices()[0].device_kind
    reduced = tracer.reduce(rec, cfg, cell["chips"], kind) if tracer else None
    return rec, stats, setup_s, memory, reduced


def run_cell(args, require_tpu: bool = True, fault=None,
             cell: dict | None = None) -> dict:
    """One run → the result line's dict (and the check lines on stderr).
    Tests pass ``require_tpu=False``, a ``cell`` of their own, and a
    ``fault`` that breaks the built server before it compiles."""
    cell = cell or load_cell(args.workload)
    cfg = cell["config"]
    enable_cache()
    from bench import check, replay, weights

    device = device_info(cell["chips"], require_tpu)
    counter = CompileCounter()
    rec, stats, setup_s, memory, reduced = serve(cell, args, counter, fault)
    gc.collect()
    result = replay.summary(rec, args.seconds)
    w = weights.make_weights(cfg, args.seed)
    checks, compared = check.checks(rec, w, cfg, args.seed, cell["limit"])
    del w
    checks["compiles_in_window"] = {"value": counter.count, "limit": 0}
    correct = check.passed(checks)

    if args.trace:
        ctx = dict(cfg=cfg, mix=cell["mix"], stats=stats, rec=rec,
                   seconds=args.seconds, trace=reduced, device=device)
        metrics = {}
        for m in cell["per_layer"]:
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(result, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    out = {"correct": correct, "attempted": result["attempted"],
           "failed": result["failed"], "metrics": metrics,
           "device": dict(device, memory_peak_bytes=memory)}
    if reduced is not None:
        out["device"].update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        out["breakdown"] = reduced.breakdown()
    # every client-side number, also those that are not metrics of the cell
    out["client"] = dict({k: None if v != v else v
                          for k, v in result.items()
                          if k not in ("attempted", "failed")},
                         compared_tokens=compared)
    out["checks"] = checks
    for k, v in checks.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args)
    except CellError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
