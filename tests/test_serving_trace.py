"""Spans and counters of the serving step (`serving.tracing`): phase
counts and times, step kinds, queue wait, pool occupancy, the reset, and
the spans in a profiler trace."""
import jax
import numpy as np
import pytest

import repro.configs as C
from repro.models import build_model
from repro.serving import GenerationEngine

PHASES = ("serve.plan", "serve.upload", "serve.dispatch", "serve.wait",
          "serve.commit")
INNER = ("serve.admit",) + PHASES


@pytest.fixture(scope="module")
def model_and_params():
    cfg = C.get_smoke_config("qwen25-05b")
    m = build_model(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(0))


def _engine(model_and_params, **kw):
    _, m, params = model_and_params
    kw = dict(dict(max_seq=64, num_slots=2, page_size=8, prefill_chunk=8),
              **kw)
    return GenerationEngine(m, params, **kw)


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lengths]


@pytest.mark.parametrize("chunked", [True, False])
def test_phase_counts_kinds_and_occupancy(model_and_params, chunked):
    cfg = model_and_params[0]
    eng = _engine(model_and_params, chunked_prefill=chunked)
    for p in _prompts(cfg, (5, 19, 11)):
        eng.submit(p, 6)
    eng.drain()
    t = eng.stats().step_times
    n = t.dispatches
    assert n > 0
    for name in PHASES + ("serve.step",):
        assert t.spans[name][0] == n, name
    assert t.spans["serve.admit"][0] == 2 * n     # the backfill included
    for count, total, longest in t.spans.values():
        assert 0 < longest <= total
    assert sum(c for c, _ in t.kinds.values()) == n
    assert sum(s for _, s in t.kinds.values()) == pytest.approx(
        t.spans["serve.step"][1])
    assert ("chunk" in t.kinds) == chunked        # one-shot only decodes
    assert t.prefills == t.queue_waits == 3
    assert t.prefill_s > 0
    assert n <= t.pool_pages_drawn < n * t.pool_pages_usable


def test_phase_sum_within_each_step(model_and_params):
    cfg = model_and_params[0]
    eng = _engine(model_and_params)
    for p in _prompts(cfg, (13, 7)):
        eng.submit(p, 5)

    def totals():
        sp = eng.stats().step_times.spans
        return (sum(sp.get(k, (0, 0.0))[1] for k in INNER),
                sp.get("serve.step", (0, 0.0))[1])

    before = totals()
    while not eng.idle:
        eng.step()
        after = totals()
        assert after[0] - before[0] <= after[1] - before[1]
        before = after


def test_queue_wait_of_a_request_behind_busy_slots(model_and_params):
    cfg = model_and_params[0]
    eng = _engine(model_and_params)
    for p in _prompts(cfg, (4, 4)):
        eng.submit(p, 8)
    eng.step()                                    # both slots taken
    eng.reset_stats()
    eng.submit(_prompts(cfg, (4,), seed=1)[0], 2)
    waited = 0.0                                  # whole steps it sat out
    while eng.stats().step_times.queue_waits == 0:
        before = eng.stats().step_times.spans.get("serve.step", (0, 0.0))[1]
        eng.step()
        t = eng.stats().step_times
        if t.queue_waits == 0:
            waited += t.spans["serve.step"][1] - before
    t = eng.stats().step_times
    assert t.queue_waits == 1
    assert waited > 0 and t.queue_wait_s > waited


def test_reset_zeroes_spans_and_counters(model_and_params):
    cfg = model_and_params[0]
    eng = _engine(model_and_params)
    eng.submit(_prompts(cfg, (9,))[0], 4)
    eng.drain()
    assert eng.stats().step_times.spans
    eng.reset_stats()
    t = eng.stats().step_times
    assert t.spans == {} and t.kinds == {}
    assert (t.queue_wait_s, t.queue_waits, t.prefill_s, t.prefills,
            t.pool_pages_drawn, t.dispatches) == (0.0, 0, 0.0, 0, 0, 0)
    assert t.pool_pages_usable > 0                # a size, not a counter


def test_spans_nest_in_serve_step_in_a_profiler_trace(model_and_params,
                                                      tmp_path):
    cfg = model_and_params[0]
    eng = _engine(model_and_params)
    eng.warmup()
    for p in _prompts(cfg, (10, 3)):
        eng.submit(p, 3)
    with jax.profiler.trace(str(tmp_path)):
        eng.drain()
    path = sorted(tmp_path.glob("**/*.xplane.pb"))[-1]
    pd = jax.profiler.ProfileData.from_file(str(path))
    spans: dict = {}
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.end_ns))
    steps = spans.pop("serve.step")
    assert len(steps) == eng.stats().step_times.dispatches
    assert set(spans) == set(INNER)
    for name, ivs in spans.items():
        for a, b in ivs:
            assert any(s0 <= a and b <= s1 for s0, s1 in steps), name
