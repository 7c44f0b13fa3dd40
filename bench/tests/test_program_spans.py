"""The program's spans and counters as the benchmark reads them: the
idle split by host phase on a small chip trace recorded with the
program's `serve.*` spans (four engine steps of the Qwen2.5-0.5B cell,
TPU v5 lite, made by record_trace.py), and the counter readers."""
import argparse
import json
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import program_spans, run, trace, work

HERE = Path(__file__).resolve().parents[1]
SPANS_TRACE = HERE / "testdata" / "qwen25-0.5b.4steps.spans.xplane.pb"
PARENT_TRACE = HERE / "testdata" / "qwen25-0.5b.4steps.xplane.pb"
CFG = json.loads((HERE / "configs" / "qwen25-0.5b-awq.json").read_text())
PHASES = ("serve.admit", "serve.plan", "serve.upload", "serve.dispatch",
          "serve.wait", "serve.commit")
COUNTERS = ("step.host_ms", "step.decode_ms", "step.chunk_ms",
            "sched.queue_wait_ms", "sched.prefill_ms", "kv.pool_used_share")


@pytest.fixture(scope="module")
def phases():
    ph = program_spans.read_file(SPANS_TRACE)
    assert ph is not None
    return ph


def _events(path, prefix):
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    return [(e.name, e.start_ns, e.end_ns) for p in pd.planes
            for line in p.lines for e in line.events
            if e.name.startswith(prefix)]


def test_every_serve_step_lies_in_a_bench_step():
    bench = [(a, b) for _, a, b in _events(SPANS_TRACE, "bench.step")]
    serve = [(a, b) for _, a, b in _events(SPANS_TRACE, "serve.step")]
    assert len(bench) == len(serve) == 4
    for a, b in serve:
        assert any(a0 <= a and b <= b0 for a0, b0 in bench)


def test_attributed_idle_within_total_idle(phases):
    assert phases.steps == phases.serve_steps == 4
    assert set(PHASES) | {"serve.step"} <= set(phases.phase_idle_s)
    assert 0 < phases.host_idle_s <= phases.idle_s
    for name, idle in phases.phase_idle_s.items():
        assert 0 <= idle <= min(phases.phase_s[name], phases.idle_s), name
    # the same idle time as the benchmark's own reduction of the trace
    red = trace.reduce_file(SPANS_TRACE, CFG, 1, work.peaks("TPU v5 lite"))
    assert phases.idle_s == pytest.approx(red.window_s - red.busy_s,
                                          rel=1e-6)


def test_a_program_without_spans_gives_nothing():
    assert program_spans.read_file(PARENT_TRACE) is None


def test_host_gap_reader(phases, tmp_path, monkeypatch):
    read = run.reader("device.host_gap_ms")
    monkeypatch.setattr(program_spans, "TRACE_DIR", tmp_path)
    assert read({"trace": object()}) is None          # no trace file
    shutil.copy(PARENT_TRACE, tmp_path / "a.xplane.pb")
    assert read({"trace": object()}) is None          # no serve.* spans
    shutil.copy(SPANS_TRACE, tmp_path / "b.xplane.pb")
    assert read({"trace": None}) is None              # not a traced run
    assert read({"trace": object()}) == pytest.approx(
        1e3 * phases.host_idle_s / 4)


def _times(**kw):
    from bench import sut
    sut._import_program()
    from repro.serving.tracing import StepTimes
    base = dict(spans={}, kinds={}, queue_wait_s=0.0, queue_waits=0,
                prefill_s=0.0, prefills=0, pool_pages_drawn=0,
                pool_pages_usable=100, dispatches=0)
    return SimpleNamespace(step_times=StepTimes(**dict(base, **kw)))


def test_counter_readers_sum_over_engines():
    a = _times(spans={"serve.step": (4, 1.0, 0.4),
                      "serve.wait": (4, 0.8, 0.3)},
               kinds={"chunk": (1, 0.4), "decode": (3, 0.6)},
               queue_wait_s=0.3, queue_waits=3, prefill_s=0.9, prefills=3,
               pool_pages_drawn=80, dispatches=4)
    b = _times(spans={"serve.step": (6, 2.0, 0.5),
                      "serve.wait": (6, 1.6, 0.4)},
               kinds={"decode": (6, 2.0)}, queue_wait_s=0.1, queue_waits=1,
               prefill_s=0.3, prefills=1, pool_pages_drawn=20, dispatches=6)
    ctx = {"stats": [a, b], "trace": None}
    got = {m: run.reader(m)(ctx) for m in COUNTERS}
    assert got == pytest.approx({
        "step.host_ms": 1e3 * 0.6 / 10, "step.decode_ms": 1e3 * 2.6 / 9,
        "step.chunk_ms": 400.0, "sched.queue_wait_ms": 100.0,
        "sched.prefill_ms": 300.0, "kv.pool_used_share": 10.0})
    # a program that keeps no step times (the parent's) reads nothing
    old = {"stats": [SimpleNamespace(dispatches=4)], "trace": None}
    assert all(run.reader(m)(old) is None for m in COUNTERS)


def test_traced_tiny_run_reports_the_counters(tiny, monkeypatch):
    """A whole traced run on the CPU: the trace has no device plane, so
    the device metrics stay silent and the counters speak."""
    from bench.tests.test_faults import cell
    monkeypatch.setattr(work, "peaks", lambda kind: {
        "flops": 1e12, "bytes_per_s": 1e11})
    args = argparse.Namespace(workload="tiny.chat", seed=2 ** 31 + 11,
                              seconds=4.0, trace=1)
    out = run.run_cell(args, require_tpu=False, cell=cell(tiny))
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert out["correct"] is True
    assert set(COUNTERS) <= set(m)
    assert "device.host_gap_ms" not in m
    assert 0 < m["kv.pool_used_share"] < 100
    assert 0 < m["step.host_ms"] < m["step.wall_ms"]
    assert m["sched.queue_wait_ms"] >= 0 and m["sched.prefill_ms"] > 0
