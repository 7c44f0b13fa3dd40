"""The trace reduction on a small trace recorded on the chip: four engine
steps of the Qwen2.5-0.5B cell (TPU v5 lite), made by record_trace.py."""
import json
from pathlib import Path

import pytest

from bench import trace, work
from bench.archs import qwen2

HERE = Path(__file__).resolve().parents[1]
TRACE = HERE / "testdata" / "qwen25-0.5b.4steps.xplane.pb"
CFG = json.loads((HERE / "configs" / "qwen25-0.5b-awq.json").read_text())
LAYERS = CFG["num_hidden_layers"]


@pytest.fixture(scope="module")
def red():
    r = trace.reduce_file(TRACE, CFG, 1, work.peaks("TPU v5 lite"))
    assert r is not None
    return r


def test_window_and_busy(red):
    assert red.steps == 4
    # four chunked-prefill steps of ~0.66 s each (TPU v5 lite)
    assert 0 < red.busy_s < red.window_s < 4.0
    idle = red.window_s - red.busy_s
    assert sum(red.gaps.values()) == pytest.approx(idle, rel=1e-6)
    assert set(red.gaps) <= set(trace.SPANS) | {"host.other"}


def test_kernel_calls_and_shares(red):
    # seven int4 linears and one paged-attention call a layer, each step
    assert red.calls["awq_matmul"] == 4 * LAYERS * 7
    assert red.calls["paged_attention_chunk"] == 4 * LAYERS
    for k in red.calls:
        share = red.roofline_share(k)
        assert 0 < share <= 100
    assert set(red.bounds["awq_matmul"]) <= {"compute", "memory"}


def test_kernel_shapes_are_the_configs(red):
    dims = set(qwen2.linear_dims(CFG).values())
    assert red.linear_dims and red.linear_dims <= dims


def test_breakdown_lists(red):
    b = red.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    names = [n for n, _ in b["device_ops"]]
    assert "awq_matmul" in names and "paged_attention_chunk" in names


def test_shapes_parser():
    text = ("%awq_matmul.57 = f32[2048,128]{1,0} custom-call(%a, %b), "
            "operand_layout_constraints={bf16[2048,896]{1,0}, "
            "s32[112,128]{1,0}, f32[14,128]{1,0}, s8[14,128]{1,0}}")
    assert trace.shapes(text)[:2] == [("f32", (2048, 128)),
                                      ("bf16", (2048, 896))]
    fl, nb = trace.kernel_work("awq_matmul", text, CFG)
    assert (fl, nb) == work.awq_matmul(2048, 896, 128, 64)
