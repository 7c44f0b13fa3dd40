"""Readings that the benchmark's limits and rates are set from; run on the
chip, never by the benchmark's own runs.

    python bench/calibrate.py limits --workload <cell> --seeds 1,2,3 [--control 1,2,3]
    python bench/calibrate.py knee --workload <cell> --rates 4,8,12 --seconds 15

``limits``: for each seed, a whole run of the cell's timed path (set-up,
ramp, window; the seconds from BENCHMARK.json), then the widest gap of
the sample that ``bench/check.py`` draws, against the plain reference:
the program's reading, and whether the run is correct by every check of
``bench/check.py`` at the cell's limit. For the seeds in ``--control`` it also reads, on the same sample, the
widest gap of the tokens that the float8 control puts first: the control's
reading. One JSON line a seed.

``knee``: one set-up, then a window at each offered rate of the cell's
open-loop mix in turn, the server drained between them. Per rate: the
client's numbers, the median TTFT of the first and the last third of the
window's requests (a backlog that grows shows as a rising TTFT), and the
requests still queued when the window closed.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402


def limits(args) -> None:
    import numpy as np

    from bench import check, weights
    cell = run.load_cell(args.workload)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    run.enable_cache()
    run.device_info(cell["chips"], True)
    controls = {int(s) for s in args.control.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        ns = argparse.Namespace(workload=args.workload, seed=seed,
                                seconds=float(args.seconds or spec["run_seconds"]), trace=0)
        counter = run.CompileCounter()
        rec, *_ = run.serve(cell, ns, counter)
        gc.collect()
        w = weights.make_weights(cell["config"], seed)
        seqs = check.compared(rec, seed)
        g = check.gaps(w, cell["config"], seqs, control=seed in controls)
        del w
        program = max(float(x[0].max()) for x in g)
        c = dict(check.counts(rec), compiles_in_window={
            "value": counter.count, "limit": 0}, max_logit_gap={
            "value": program, "limit": cell["limit"]["max_logit_gap"]})
        out = {"seed": seed, "correct": check.passed(c), "program": program,
               "tokens": int(sum(len(x[0]) for x in g)),
               "finished": sum(s.output is not None
                               for s in rec.sent.values()),
               "longest": max(len(x[1]) for x in seqs),
               "deepest": max(len(x[0]) + len(x[1]) for x in seqs),
               "seconds": time.perf_counter() - t,
               "checks": {k: v["value"] for k, v in c.items()}}
        if seed in controls:
            out["control"] = max(float(x[1].max()) for x in g)
            out["control_median"] = float(np.median(
                np.concatenate([x[1] for x in g])))
        print(json.dumps(out), flush=True)


def knee(args) -> None:
    import numpy as np

    from bench import loadgen, replay, sut, weights
    cell = run.load_cell(args.workload)
    run.enable_cache()
    run.device_info(cell["chips"], True)
    cfg, mix = cell["config"], dict(cell["mix"])
    w = weights.make_weights(cfg, args.seed)
    server, engines = sut.build(cfg, w)
    server.warmup()
    for rate in (float(r) for r in args.rates.split(",")):
        mix["rate_per_s"] = rate
        reqs = loadgen.generate(mix, cfg["vocab_size"], args.seed,
                                args.seconds)
        queued = []
        rec = replay.run(server, reqs, mix, args.seconds, grace_s=0.0,
                         on_window_start=lambda t: server.reset_stats(),
                         on_window_end=lambda t: queued.extend(
                             e.stats().queue_depth for e in engines))
        server.drain()
        s = replay.summary(rec, args.seconds)
        att = sorted((x for x in rec.sent.values() if x.in_window),
                     key=lambda x: x.t_ref)
        third = max(1, len(att) // 3)
        ttft = [[1e3 * (x.t_first - x.t_ref) for x in part
                 if x.t_first is not None]
                for part in (att[:third], att[-third:])]
        print(json.dumps(dict(
            s, rate=rate, queued_at_close=sum(queued),
            ttft_first_third_ms=float(np.median(ttft[0])) if ttft[0] else None,
            ttft_last_third_ms=float(np.median(ttft[1])) if ttft[1] else None,
        )), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    a = sub.add_parser("limits")
    a.add_argument("--workload", required=True)
    a.add_argument("--seeds", required=True)
    a.add_argument("--control", default="")
    a.add_argument("--seconds", type=float, default=0.0,
                   help="window length (default: run_seconds)")
    b = sub.add_parser("knee")
    b.add_argument("--workload", required=True)
    b.add_argument("--rates", required=True)
    b.add_argument("--seconds", type=float, default=15.0)
    b.add_argument("--seed", type=int, default=2 ** 31 + 101)
    args = ap.parse_args(argv)
    {"limits": limits, "knee": knee}[args.cmd](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
