"""The plain reference against the program's serving path, at a tiny
size on the CPU, on the same packed weights.

The engine serves greedy streams through chunked prefill, int8 pages and
prefix-aliased pages; the reference recomputes each prompt + stream in
float32 with no cache. Every served token must sit at the top of the
reference's logits to within rounding, a random stream must not, and the
float8 control must read at least three times the program's widest gap.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, sut, weights
from bench.archs import qwen2 as qwen2_arch
from bench.reference import qwen2

SEED = 2 ** 31 + 11
# bf16 activations and int8 pages against float32: the widest gap seen at
# this size is ~0.01 (tied and untied); a random token sits ~1 below
GAP = 0.05


def serve(cfg, w, prompts, max_new=24, prefix=None):
    server, _ = sut.build(cfg, w)
    rids = [server.submit(p, max_new, prefix_id=prefix) for p in prompts]
    out = server.drain()
    return [out[r] for r in rids]


def prompts(n_prefix=0):
    rng = np.random.default_rng(0)
    pre = rng.integers(0, 512, n_prefix).astype(np.int32)
    return [np.concatenate([pre, rng.integers(0, 512, n).astype(np.int32)])
            for n in (5, 17, 40, 70)]


def test_dequant_matches_program(tiny):
    w = weights.make_weights(tiny, SEED)
    params = qwen2_arch.program_params(tiny, w)
    from repro.core.packing import dequantize_packed
    p = params["segments"]["seg_0"]["mlp"]["down"]
    one = type(p)(p.qweight[1], p.scales[1], p.zeros[1], p.input_scale[1],
                  None, p.group_size)
    lw = {k: v[1] for k, v in w["layers"]["down"].items()}
    np.testing.assert_array_equal(
        np.asarray(qwen2.dequant(lw, 64)),
        np.asarray(dequantize_packed(one, jnp.float32)))


@pytest.mark.parametrize("tied", [True, False])
@pytest.mark.parametrize("prefix", [None, "sys"])
def test_served_streams_match_reference(tiny, tied, prefix):
    cfg = dict(tiny, tie_word_embeddings=tied)
    w = weights.make_weights(cfg, SEED)
    ps = prompts(n_prefix=48 if prefix else 0)
    outs = serve(cfg, w, ps, prefix=prefix)
    g = check.gaps(w, cfg, list(zip(ps, outs)))
    assert max(float(x[0].max()) for x in g) < GAP
    rng = np.random.default_rng(1)
    junk = [(p, rng.integers(0, 512, len(o)).astype(np.int32))
            for p, o in zip(ps, outs)]
    assert min(float(x[0].max()) for x in check.gaps(w, cfg, junk)) > GAP


@pytest.mark.parametrize("tied", [True, False])
def test_float8_control_reads_three_times_the_program(tiny, tied):
    cfg = dict(tiny, tie_word_embeddings=tied)
    w = weights.make_weights(cfg, SEED)
    ps = prompts()
    g = check.gaps(w, cfg, list(zip(ps, serve(cfg, w, ps))), control=True)
    program = max(float(x[0].max()) for x in g)
    control = max(float(x[1].max()) for x in g)
    assert control > 3 * program
    assert control > GAP
