"""Compile the serving path's Pallas kernels for a TPU v5e that is
described, not attached, at Qwen2.5-0.5B widths (d 896, 14/2 heads,
head_dim 64, d_ff 4864, GS 64).

Interpret mode on CPU cannot see what Mosaic refuses (block shapes off
the (8, 128) tiling, casts the chip lacks, VMEM overruns); this compile
can. Nothing runs, so it says nothing about results or time. The
topology is described inside a fixture — never at import — so every
pytest worker collects the same tests and only the one running this file
loads the TPU compiler.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.packing import PackedLinear
from repro.core.qlinear import qlinear_apply
from repro.distributed.sharding import use_mesh
from repro.kernels import ops
from repro.kernels.awq_matmul import awq_matmul_pallas
from repro.kernels.paged_attention import (paged_attention_chunk,
                                           paged_attention_chunk_sharded)

D, HEADS, KV_HEADS, HEAD_DIM, D_FF, GS = 896, 14, 2, 64, 4864, 64
PAGE, SLOTS, PAGES_PER_SLOT, NUM_PAGES, CHUNK = 16, 8, 8, 65, 32
# (K, N) of every AWQ projection: q/o, k/v, gate/up, down
PROJECTIONS = [(D, HEADS * HEAD_DIM), (D, KV_HEADS * HEAD_DIM),
               (D, D_FF), (D_FF, D)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                    # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _packed(k, n, sharding, tp=None):
    return PackedLinear(
        qweight=_sds((k // 8, n), jnp.int32, sharding(P(None, None))),
        scales=_sds((k // GS, n), jnp.float32, sharding(P(None, None))),
        zeros=_sds((k // GS, n), jnp.int8, sharding(P(None, None))),
        input_scale=_sds((k,), jnp.float32, sharding(P(None))),
        bias=None, group_size=GS, tp=tp)


@pytest.mark.parametrize("m", [8, 256], ids=["decode", "prefill"])
@pytest.mark.parametrize("k,n", PROJECTIONS,
                         ids=["q_o", "k_v", "gate_up", "down"])
def test_awq_matmul_compiles_for_v5e(one_chip, m, k, n):
    bm, bn, bk = ops.choose_blocks(m, k, n, GS)
    fn = jax.jit(lambda x, q, s, z: awq_matmul_pallas(
        x, q, s, z, group_size=GS, block_m=bm, block_n=bn, block_k=bk))
    compiled = fn.lower(
        _sds((-(-m // bm) * bm, k), jnp.bfloat16, one_chip),
        _sds((k // 8, n), jnp.int32, one_chip),
        _sds((k // GS, n), jnp.float32, one_chip),
        _sds((k // GS, n), jnp.int8, one_chip)).compile()
    assert _custom_calls(compiled) == 1


def _paged_args(c, sharding, head_axis=None):
    """Shapes of one layer's int8 pool + a [SLOTS, c] query block."""
    g = HEADS // KV_HEADS
    hp = P(None, head_axis, None)
    return (_sds((SLOTS, c, KV_HEADS, g, HEAD_DIM), jnp.float32,
                 sharding(P(None, None, head_axis, None, None))),
            _sds((NUM_PAGES, KV_HEADS, PAGE, HEAD_DIM), jnp.int8,
                 sharding(P(*hp, None))),
            _sds((NUM_PAGES, KV_HEADS, PAGE), jnp.float32, sharding(hp)),
            _sds((NUM_PAGES, KV_HEADS, PAGE, HEAD_DIM), jnp.int8,
                 sharding(P(*hp, None))),
            _sds((NUM_PAGES, KV_HEADS, PAGE), jnp.float32, sharding(hp)),
            _sds((SLOTS, PAGES_PER_SLOT), jnp.int32, sharding(P(None, None))),
            _sds((SLOTS, c), jnp.int32, sharding(P(None, None))))


@pytest.mark.parametrize("c", [1, CHUNK], ids=["decode", "chunk"])
def test_paged_attention_compiles_for_v5e(one_chip, c):
    compiled = jax.jit(paged_attention_chunk).lower(
        *_paged_args(c, lambda spec: one_chip)).compile()
    assert _custom_calls(compiled) == 1


@pytest.fixture(scope="module")
def tp2_mesh(topo):
    """The model axis of one TP-2 serving replica on a 2x2 host."""
    return Mesh(np.asarray(topo.devices[:2]), ("model",))


@pytest.mark.parametrize("c", [1, CHUNK], ids=["decode", "chunk"])
def test_sharded_paged_attention_compiles_for_v5e(tp2_mesh, c):
    """`shard_map` over KV heads keeps the kernel on each chip."""
    args = _paged_args(c, lambda spec: NamedSharding(tp2_mesh, spec),
                       head_axis="model")
    fn = jax.jit(lambda *a: paged_attention_chunk_sharded(*a,
                                                          mesh=tp2_mesh))
    compiled = fn.lower(*args).compile()
    assert _custom_calls(compiled) == 1


@pytest.mark.parametrize("tp,k,n", [("col", D, D_FF), ("row", D_FF, D)],
                         ids=["col_gate_up", "row_down"])
def test_sharded_awq_matmul_compiles_for_v5e(tp2_mesh, tp, k, n):
    """Column- and row-parallel AWQ layers keep the kernel per shard."""
    w = P(None, "model") if tp == "col" else P("model", None)

    def sharding(spec):
        if len(spec) == 2:
            spec = w
        elif tp == "row":
            spec = P("model")
        return NamedSharding(tp2_mesh, spec)

    p = _packed(k, n, sharding, tp=tp)
    x = _sds((SLOTS * CHUNK, k), jnp.bfloat16,
             NamedSharding(tp2_mesh, P(None, None)))

    def fn(p, x):
        with use_mesh(tp2_mesh):
            return qlinear_apply(p, x, impl="kernel")
    compiled = jax.jit(fn).lower(p, x).compile()
    assert _custom_calls(compiled) == 1
