"""Compile the serving path's Pallas kernels for a TPU v5e that is
described, not attached, at Qwen2.5-0.5B widths (d 896, 14/2 heads,
head_dim 64, d_ff 4864, GS 64); the paged-attention kernel also at
Qwen2.5-7B's attention shapes (28/4 heads, head_dim 128) over a 128-page
context bucket.

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
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


# (chunk width, KV heads, query heads, head_dim, context-bucket pages,
# pool pages) of one attention layer's call
PAGED_SHAPES = {
    "decode": (1, KV_HEADS, HEADS, HEAD_DIM, PAGES_PER_SLOT, NUM_PAGES),
    "chunk": (CHUNK, KV_HEADS, HEADS, HEAD_DIM, PAGES_PER_SLOT, NUM_PAGES),
    "7b_chunk": (16, 4, 28, 128, 128, 32 * 128 + 1),
}


def _paged_args(shape, sharding, head_axis=None):
    """Shapes of one layer's int8 pool + a [SLOTS, c] query block."""
    c, hkv, heads, hd, n_blocks, n_pages = shape
    hp = P(None, head_axis, None)
    return (_sds((SLOTS, c, hkv, heads // hkv, hd), jnp.float32,
                 sharding(P(None, None, head_axis, None, None))),
            _sds((n_pages, hkv, PAGE, hd), jnp.int8, sharding(P(*hp, None))),
            _sds((n_pages, hkv, PAGE), jnp.float32, sharding(hp)),
            _sds((n_pages, hkv, PAGE, hd), jnp.int8, sharding(P(*hp, None))),
            _sds((n_pages, hkv, PAGE), jnp.float32, sharding(hp)),
            _sds((SLOTS, n_blocks), jnp.int32, sharding(P(None, None))),
            _sds((SLOTS, c), jnp.int32, sharding(P(None, None))))


@pytest.mark.parametrize("shape", PAGED_SHAPES.values(), ids=PAGED_SHAPES)
def test_paged_attention_compiles_for_v5e(one_chip, shape):
    compiled = jax.jit(paged_attention_chunk).lower(
        *_paged_args(shape, lambda spec: one_chip)).compile()
    assert _custom_calls(compiled) == 1


@pytest.mark.parametrize("page_shape,dtype", [
    ((KV_HEADS, PAGE, HEAD_DIM), jnp.int8),      # 0.5B K/V codes, hd 64
    ((KV_HEADS, PAGE), jnp.float32),             # a page's scale strips
], ids=["codes_hd64", "scales"])
def test_page_copy_out_of_hbm_is_refused_for_v5e(one_chip, page_shape,
                                                 dtype):
    """Why `paged_attention_chunk` takes each page of a block as operands
    of its own: a copy started by hand of one page out of a pool left in
    HBM (``memory_space=pl.ANY``) does not compile where the page's last
    dim is under the 128-lane tile. If this starts to compile, the pools
    can go back to one operand each behind manual DMAs."""
    ppb, n_blocks = 4, 8

    def kernel(tables_ref, pool_hbm, o_ref, buf, sem):
        b, j = pl.program_id(0), pl.program_id(1)
        copies = [pltpu.make_async_copy(
            pool_hbm.at[tables_ref[b * n_blocks + j * ppb + i]],
            buf.at[i], sem) for i in range(ppb)]
        for cp in copies:
            cp.start()
        for cp in copies:
            cp.wait()
        o_ref[...] = jnp.sum(buf[...].astype(jnp.float32), axis=0)[None]

    zeros = (0,) * len(page_shape)
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(SLOTS, n_blocks // ppb),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, *page_shape),
                                   lambda b, j, t: (b, *zeros)),
            scratch_shapes=[pltpu.VMEM((ppb, *page_shape), dtype),
                            pltpu.SemaphoreType.DMA]),
        out_shape=jax.ShapeDtypeStruct((SLOTS, *page_shape), jnp.float32))
    lowered = jax.jit(call).lower(
        _sds((SLOTS * n_blocks,), jnp.int32, one_chip),
        _sds((NUM_PAGES, *page_shape), dtype, one_chip))
    with pytest.raises(Exception, match="aligned to tiling"):
        lowered.compile()


@pytest.fixture(scope="module")
def tp2_mesh(topo):
    """The model axis of one TP-2 serving replica on a 2x2 host."""
    return Mesh(np.asarray(topo.devices[:2]), ("model",))


@pytest.mark.parametrize("shape", PAGED_SHAPES.values(), ids=PAGED_SHAPES)
def test_sharded_paged_attention_compiles_for_v5e(tp2_mesh, shape):
    """`shard_map` over KV heads keeps the kernel on each chip."""
    args = _paged_args(shape, lambda spec: NamedSharding(tp2_mesh, spec),
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
