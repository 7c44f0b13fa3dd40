"""Quantized-linear application — the runtime half of the paper's technique.

``qlinear_apply`` is the single dispatch point between:

  * ``ref``    — pure-jnp unpack → dequant → matmul. This is what the
                 multi-pod dry-run lowers (XLA sees the real int32 weight
                 stream, so `cost_analysis` reflects the ~3.56× weight-byte
                 reduction), and the oracle the Pallas kernel is tested
                 against.
  * ``kernel`` — the Pallas fused unpack+dequant+MAC kernel
                 (`repro.kernels`), the TPU analogue of the paper's
                 MACRO_MAC units. On CPU it runs in interpret mode (tests).

The hybrid execution strategy of the paper (§III: MACs on the FPGA fabric,
non-linear ops on the CPU) maps to: every quantized matmul goes through this
module (MXU pipeline), while RoPE/RMSNorm/SiLU stay as plain XLA ops on the
VPU. `ExecutionConfig.offload_min_flops` implements the paper's
"intelligently offloads compute-intensive operations" knob: matmuls below
the threshold stay on the generic path (for tiny decode GEMVs the kernel
launch overhead is not worth it on either platform).
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp

from repro.core.packing import PackedLinear, dequantize_packed


@dataclasses.dataclass(frozen=True)
class ExecutionConfig:
    """Runtime knobs for the quantized path.

    Frozen/hashable on purpose: the config is read at TRACE time, so a
    compiled function bakes in whatever was active when it was traced.
    Callers that jit must therefore treat the config as part of the
    compilation key — `serving.GenerationEngine` keys every compiled
    dispatch on the active config (so `set_execution_config` takes
    effect on the next step, triggering a retrace), and one-off callers
    can pass ``cfg=`` to `qlinear_apply` explicitly.
    """

    impl: str = "auto"              # "auto" | "ref" | "kernel" | "kernel_interpret"
    compute_dtype: jnp.dtype = jnp.bfloat16
    offload_min_flops: float = 2 ** 20  # hybrid threshold (paper §III)


_EXEC = ExecutionConfig()


def set_execution_config(**kw) -> ExecutionConfig:
    global _EXEC
    _EXEC = dataclasses.replace(_EXEC, **kw)
    return _EXEC


def get_execution_config() -> ExecutionConfig:
    return _EXEC


@contextlib.contextmanager
def execution_config(cfg: ExecutionConfig):
    """Pin the ambient execution config for the duration of the block.

    Trace-scoped: wrap the *tracing* of a jitted function so every
    `qlinear_apply` inside it sees ``cfg`` instead of the mutable global
    (which a finished trace would otherwise have captured silently).
    """
    global _EXEC
    prev, _EXEC = _EXEC, cfg
    try:
        yield cfg
    finally:
        _EXEC = prev


def _resolve_impl(impl: str) -> str:
    if impl != "auto":
        return impl
    platform = jax.default_backend()
    return "kernel" if platform == "tpu" else "ref"


def _kernel_matmul(x2: jax.Array, p: PackedLinear, compute_dtype, *,
                   interpret: bool) -> jax.Array:
    """The fused Pallas kernel, per shard when a serving mesh splits ``p``.

    The compiler cannot partition a ``pallas_call``, so on a mesh whose
    ``model`` axis splits the layer (``p.tp``) the kernel runs under
    `jax.shard_map`: column-parallel layers multiply the whole activation
    by their N-shard, row-parallel ones their K-slice of it by their
    K-shard and sum the partial products over ``model``.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import current_mesh
    from repro.kernels import ops as kops  # lazy: avoid circular import

    def run(x_, q_, s_, z_):
        local = dataclasses.replace(p, qweight=q_, scales=s_, zeros=z_)
        return kops.awq_matmul(x_, local, compute_dtype=compute_dtype,
                               interpret=interpret)

    mesh = current_mesh()
    if p.tp is None or mesh is None or mesh.shape.get("model", 1) == 1:
        return run(x2, p.qweight, p.scales, p.zeros)
    if p.tp == "col":
        w = P(None, "model")
        return jax.shard_map(run, mesh=mesh, in_specs=(P(), w, w, w),
                             out_specs=w, check_vma=False)(
            x2, p.qweight, p.scales, p.zeros)
    w = P("model", None)
    return jax.shard_map(
        lambda *a: jax.lax.psum(run(*a), "model"), mesh=mesh,
        in_specs=(P(None, "model"), w, w, w), out_specs=P(),
        check_vma=False)(x2, p.qweight, p.scales, p.zeros)


def qlinear_apply(p: PackedLinear, x: jax.Array,
                  impl: str | None = None,
                  cfg: ExecutionConfig | None = None) -> jax.Array:
    """``y = (x * input_scale) @ dequant(qweight) + bias``.

    ``x``: [..., K]; returns [..., N] in x.dtype. ``cfg`` defaults to the
    ambient config (see `execution_config` for the trace-time contract).
    """
    cfg = cfg if cfg is not None else _EXEC
    impl = _resolve_impl(impl or cfg.impl)
    orig_dtype = x.dtype
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)

    # AWQ inverse activation scale (explicit form; foldable into the
    # producing norm — see core/awq.fold_into_norm).
    x2 = (x2.astype(jnp.float32) * p.input_scale[None, :]).astype(
        cfg.compute_dtype)

    m = x2.shape[0]
    flops = 2.0 * m * k * p.n
    if impl == "kernel" and flops < cfg.offload_min_flops:
        impl = "ref"  # hybrid threshold: tiny GEMV stays on the generic path

    if impl in ("kernel", "kernel_interpret"):
        y = _kernel_matmul(x2, p, cfg.compute_dtype,
                           interpret=(impl == "kernel_interpret"))
    else:
        w = dequantize_packed(p, cfg.compute_dtype)
        y = jnp.dot(x2, w, preferred_element_type=jnp.float32)

    y = y.astype(orig_dtype)
    if p.bias is not None:
        y = y + p.bias.astype(orig_dtype)
    return y.reshape(*lead, p.n)
