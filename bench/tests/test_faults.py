"""A whole run, with the chip check skipped, at a tiny size on the CPU:
sound, it reads correct; with the timed path broken underneath, it reads
not correct. The faults a served cell can have: a token altered where the
step produces it, and a step that returns its state (the KV pages)
unchanged."""
import argparse
import json

import pytest

from bench import run

MIX = {"loop": "open", "order": "fixed", "arrivals": "poisson",
       "rate_per_s": 6.0,
       "ramp_s": 1, "prefixes": {"count": 4, "tokens": 64, "zipf_s": 1.1},
       "prompt": {"median": 32, "sigma": 0.8, "min": 8, "max": 128},
       "output": {"median": 16, "sigma": 0.8, "min": 4, "max": 64}}


def cell(cfg):
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    return {"name": "tiny.chat", "chips": 1, "config": cfg, "mix": MIX,
            "limit": {"max_logit_gap": 0.05},
            "end_to_end": spec["end_to_end"], "per_layer": spec["per_layer"]}


def altered_token(server, engines):
    eng = engines[0]
    step = eng._chunk_greedy_fn

    def broken(*a):
        tok, cache = step(*a)
        return tok.at[0].set((tok[0] + 1) % eng.cfg.vocab_size), cache
    eng._chunk_greedy_fn = broken


def state_unchanged(server, engines):
    eng = engines[0]
    step = eng._chunk_greedy_fn

    def broken(params, cache, *a):
        tok, _ = step(params, cache, *a)
        return tok, cache
    eng._chunk_greedy_fn = broken


@pytest.mark.parametrize("fault,correct", [
    (None, True), (altered_token, False), (state_unchanged, False)])
def test_run_reads_the_fault(tiny, fault, correct, capsys):
    args = argparse.Namespace(workload="tiny.chat", seed=2 ** 31 + 5,
                              seconds=4.0, trace=0)
    out = run.run_cell(args, require_tpu=False, fault=fault,
                       cell=cell(tiny))
    assert out["correct"] is correct
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"tokens_per_s", "ttft_p95_ms",
                                   "itl_p50_ms", "itl_p95_ms", "setup_s"}
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-1].startswith("check ")


def test_router_over_one_chip_replicas(tiny, capsys):
    cfg = dict(tiny, serving=dict(tiny["serving"], replicas=2))
    args = argparse.Namespace(workload="tiny.chat", seed=2 ** 31 + 6,
                              seconds=4.0, trace=0)
    out = run.run_cell(args, require_tpu=False, cell=cell(cfg))
    assert out["correct"] is True and out["attempted"] > 0
