"""Architectures and kernels found by name: the Qwen2 architecture module
makes the weights the harness made before it was one, bit for bit; the
trace reduction, counting each kernel through its own module, reads what
it read before on both recorded chip traces; a missing file is a
`CellError` that names it."""
import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import check, plugins, run, sut, trace, weights, work
from bench.archs import qwen2

HERE = Path(__file__).resolve().parents[1]
CFG = json.loads((HERE / "configs" / "qwen25-0.5b-awq.json").read_text())


def digest(w) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(w)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


# sha256 of `weights.make_weights` for the tiny configuration, read before
# the weight layout moved into bench/archs/qwen2.py
@pytest.mark.parametrize("tied,seed,want", [
    (True, 7,
     "3df54b949db7a19db063d6865c8ca5b17a4e01cc84bffd661204cc2d602c0693"),
    (True, 2 ** 33 + 5,
     "64669afd0158dff9f3eaed3087fed66ddc6a930d1774337fc2c64db57650e4ce"),
    (False, 7,
     "b67c8a180e4efa4f15af7292c892761bb6a9982a3503196e2d6c957b53effc04"),
    (False, 2 ** 33 + 5,
     "f07b0e54143a852ad73f4273f477b56e6e9bbaa04b5b2e523f3dfe8be0225879"),
])
def test_qwen2_weights_are_bit_identical(tiny, tied, seed, want):
    cfg = dict(tiny, tie_word_embeddings=tied)
    assert digest(qwen2.make_weights(cfg, seed)) == want
    assert digest(weights.make_weights(cfg, seed)) == want


# the reduction of each recorded trace before kernels were modules:
# busy_s, window_s, and per kernel calls, [roofline s, device s], bounds
PINNED = {
    "qwen25-0.5b.4steps.xplane.pb": (2.621805819, 2.642409572, {
        "awq_matmul": (672, [0.03042343967717135, 0.07610896600000001],
                       {"memory": 192, "compute": 480}),
        "paged_attention_chunk": (
            96, [0.0018313566135228453, 2.347118666000002],
            {"compute": 96})}),
    "qwen25-0.5b.4steps.spans.xplane.pb": (
        0.33768854800000003, 0.354834105, {
            "awq_matmul": (672, [0.007617517765446718, 0.01915187499999995],
                           {"memory": 192, "compute": 480}),
            "paged_attention_chunk": (
                96, [0.0008450063003663002, 0.20938873800000019],
                {"memory": 96})}),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_reduction_reads_as_before(name):
    red = trace.reduce_file(HERE / "testdata" / name, CFG, 1,
                            work.peaks("TPU v5 lite"))
    busy, window, kernels = PINNED[name]
    assert (red.busy_s, red.window_s) == pytest.approx((busy, window),
                                                       rel=1e-12)
    assert set(red.calls) == set(kernels) and set(kernels) <= trace.KERNELS
    for k, (calls, secs, bounds) in kernels.items():
        assert red.calls[k] == calls
        assert red.kernels[k] == pytest.approx(secs, rel=1e-12)
        assert red.bounds[k] == bounds


def test_missing_arch_reference_or_kernel_is_a_cell_error(tiny):
    cfg = dict(tiny, reference="no_such_arch")
    for call in (lambda: weights.make_weights(cfg, 1),
                 lambda: sut.build(cfg, {}),
                 lambda: work.token_flops(cfg),
                 lambda: check.gaps({}, cfg, [])):
        with pytest.raises(run.CellError, match="no_such_arch.py"):
            call()
    with pytest.raises(run.CellError, match="no_such_kernel.py"):
        trace.kernel_work("no_such_kernel", "", CFG)
    with pytest.raises(run.CellError, match="is missing"):
        plugins.module("archs", "../reference/qwen2")


def test_kernels_are_the_modules_that_count_them():
    assert {"awq_matmul", "paged_attention_chunk"} <= trace.KERNELS
    assert all(callable(plugins.kernel(k).work) for k in trace.KERNELS)
