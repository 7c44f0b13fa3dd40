"""Record the small chip trace that test_trace.py reduces (run on a TPU):

    python bench/tests/record_trace.py [out.xplane.pb]

Builds the Qwen2.5-0.5B cell's engine as a run does, puts 40 requests in
flight (prefill chunks beside decode rows), and traces four steps with the
benchmark's own host spans, as `bench/trace.py` does in a run. Writes
``bench/testdata/qwen25-0.5b.4steps.xplane.pb`` unless told otherwise.
"""
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

STEPS = 4
OUT = ROOT / "bench" / "testdata" / "qwen25-0.5b.4steps.xplane.pb"


def main() -> int:
    from bench import run
    run.enable_cache()
    import jax
    import numpy as np

    from bench import replay, sut, weights
    run.device_info(1, True)
    cfg = json.loads((ROOT / "bench/configs/qwen25-0.5b-awq.json")
                     .read_text())
    w = weights.make_weights(cfg, 2 ** 31 + 7)
    server, _ = sut.build(cfg, w)
    server.warmup()
    rng = np.random.default_rng(0)
    for n in rng.integers(16, 400, 40):
        server.submit(rng.integers(0, cfg["vocab_size"], n, dtype=np.int32),
                      64)
    for _ in range(3):
        server.step()
    tmp = ROOT / ".bench_trace"
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    spans = replay.Spans(replay.Record(), annotate=True)
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    for _ in range(STEPS):
        with spans("bench.step"):
            server.step()
        with spans("bench.collect"):
            server.collect()
    jax.profiler.stop_trace()
    src = sorted(tmp.glob("**/*.xplane.pb"))[-1]
    out = Path(sys.argv[1]) if len(sys.argv) > 1 else OUT
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out)
    print(f"wrote {out} ({out.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
