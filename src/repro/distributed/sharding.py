"""Sharding rules: logical axes → the (pod, data, model) production mesh.

Design (DESIGN.md §5):
  * DP spans ``pod × data`` (the pod axis only ever carries gradient
    all-reduce in training; serving treats pods as independent replicas).
  * TP spans ``model``: column-parallel QKV/gate/up, row-parallel O/down,
    vocab-parallel embedding/lm_head, expert-FFN dim for MoE.
  * Decode KV caches are sequence-sharded over ``model`` (SP-decode): at
    decode_32k/long_500k batch sizes the cache, not the weights, dominates
    per-chip HBM, and sequence sharding keeps softmax/attention communication
    to three tiny all-reduces per layer.

Every rule checks divisibility against the actual mesh axis sizes and falls
back to replication — head counts like hymba's 25 or vocabs like 32001 are
not forced onto a 16-way axis (the fallback is recorded by the dry-run's
memory analysis, not hidden).

Quantized params: a `PackedLinear`'s qweight [K/8, N], scales/zeros [K/GS, N]
and input_scale [K] inherit the parent linear's K/N sharding, so the packed
INT4 stream shards exactly like the float weight it replaces (the paper's
AWQ_MACRO blocks stay intact per device because every shard keeps whole
quant groups: K/8 and K/GS divide evenly whenever K does).
"""
from __future__ import annotations

import contextlib
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_MESH: Mesh | None = None


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None):
    """Activate a mesh for `constrain` calls inside model code."""
    global _MESH
    prev = _MESH
    _MESH = mesh
    try:
        yield mesh
    finally:
        _MESH = prev


def current_mesh() -> Mesh | None:
    return _MESH


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    """Mesh axes that jointly carry the batch (DP) dimension."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


# Logical activation axes → mesh axes. Several logical names map to the same
# mesh axis ("model"); `_resolve` allocates greedily in dimension order and
# never assigns one mesh axis twice.
LOGICAL_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "heads": ("model",),
    "kv_heads": ("model",),
    "q_groups": ("model",),
    "ffn": ("model",),
    "vocab": ("model",),
    "cache_seq": ("model",),
    "seq": ("model",),       # sequence parallelism (long-context prefill)
    "model": ("model",),
    "expert_cap": ("pod", "data"),
    "ssm_inner": ("model",),
}


def _resolve(mesh: Mesh, logical: tuple, shape: tuple[int, ...]) -> P:
    """Map logical axes → PartitionSpec.

    Drops axes that are absent from the mesh, don't divide the dimension, or
    were already assigned to an earlier dimension (first match wins).
    """
    out = []
    used: set[str] = set()
    for dim, name in zip(shape, logical):
        if name is None:
            out.append(None)
            continue
        axes = LOGICAL_RULES.get(name, (name,))
        axes = tuple(a for a in axes
                     if a in mesh.axis_names and a not in used)
        if axes and dim % _axis_size(mesh, axes) == 0:
            used.update(axes)
            out.append(axes if len(axes) > 1 else axes[0])
        else:
            out.append(None)
    return P(*out)


def constrain(x: jax.Array, logical: tuple) -> jax.Array:
    """`with_sharding_constraint` by logical axis names (no-op without mesh)."""
    mesh = _MESH
    if mesh is None:
        return x
    spec = _resolve(mesh, logical, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Parameter sharding rules (path-based)
# ---------------------------------------------------------------------------

# Column-parallel (shard output/N dim) vs row-parallel (shard input/K dim).
_COL_LINEARS = ("wq", "wk", "wv", "gate", "up", "wz", "wx", "wb", "wc",
                "wdt", "q_proj", "kv_down", "kv_up", "patch_proj",
                "frame_proj")
_ROW_LINEARS = ("wo", "down", "out_proj")


def _linear_axes(parent: str, k: int, n: int, mesh: Mesh, cfg=None
                 ) -> tuple[str | None, str | None]:
    """(K-axis, N-axis) logical sharding for a linear named ``parent``."""
    msize = mesh.shape.get("model", 1)
    if parent in _ROW_LINEARS:
        return ("model" if k % msize == 0 else None), None
    if parent in _COL_LINEARS:
        # Attention projections only shard if whole heads land per device —
        # otherwise replicate (divisibility rule; see module docstring).
        if cfg is not None and parent in ("wq", "wk", "wv"):
            heads = cfg.num_heads if parent == "wq" else cfg.num_kv_heads
            if heads % msize != 0:
                return None, None
        return None, ("model" if n % msize == 0 else None)
    return None, None


def param_pspec(path: str, leaf: Any, mesh: Mesh, cfg=None) -> P:
    """PartitionSpec for one param leaf addressed by its tree path.

    Handles float linears (``.../<name>/w``), PackedLinear leaves
    (``.../<name>/qweight`` etc.), embeddings, norms and stacked leading
    layer dims (spec is right-aligned; leading dims unsharded).
    """
    shape = tuple(leaf.shape)
    parts = path.split("/")
    leafname = parts[-1]
    parent = parts[-2] if len(parts) >= 2 else ""
    msize = mesh.shape.get("model", 1)

    def pad(spec_tail: list, ndim_tail: int) -> P:
        return P(*([None] * (len(shape) - ndim_tail) + spec_tail))

    if "embed" in path and leafname == "table":
        v, d = shape[-2], shape[-1]
        if v % msize == 0:
            return pad(["model", None], 2)
        if d % msize == 0:
            return pad([None, "model"], 2)
        return P(*([None] * len(shape)))

    if parent == "lm_head" and leafname == "w":
        d, v = shape[-2], shape[-1]
        return pad([None, "model" if v % msize == 0 else None], 2)

    if parent == "experts" or (len(parts) >= 3 and parts[-3] == "experts"):
        # experts/<gate|up|down>/w with shape [..., E, K, N]
        name = parent if leafname == "w" else parts[-2]
        if leafname in ("w", "qweight", "scales", "zeros"):
            if name in ("gate", "up"):
                ax = "model" if shape[-1] % msize == 0 else None
                return pad([None, None, ax], 3)
            if name == "down":
                if leafname == "w":  # float (training): row-parallel on F
                    ax = "model" if shape[-2] % msize == 0 else None
                    return pad([None, ax, None], 3)
                # packed (serving): F-sharding would split quant groups
                # (F/|model| rarely a GS multiple) — shard the OUTPUT dim
                # instead; dequant then stays shard-local (§Perf B4).
                ax = "model" if shape[-1] % msize == 0 else None
                return pad([None, None, ax], 3)
        if leafname == "input_scale":
            # replicated: applied to the (gathered) full-K activations
            return P(*([None] * len(shape)))
        return P(*([None] * len(shape)))

    if leafname in ("w", "qweight", "scales", "zeros") and len(shape) >= 2:
        k_ax, n_ax = _linear_axes(parent, shape[-2], shape[-1], mesh, cfg)
        if leafname != "w" and k_ax is not None:
            # Quantized row-parallel linear: each K-shard must hold WHOLE
            # dequant groups (the AWQ_MACRO invariant), or the group-reshape
            # un-shards the weight and XLA gathers it every step (§Perf A2).
            # rows → K: qweight packs PACK/row, scales/zeros are per-group.
            # The group size comes from the quant config (cfg override or
            # the pipeline default), not a magic literal.
            from repro.core.packing import PACK
            from repro.core.quantize import QuantConfig
            gs = (getattr(cfg, "quant_group_size", None)
                  or QuantConfig().group_size)
            k_full = shape[-2] * (PACK if leafname == "qweight" else gs)
            if (k_full // msize) % gs != 0:
                # flip to column-parallel (tiny output all-gather instead)
                k_ax = None
                n_ax = "model" if shape[-1] % msize == 0 else None
        if k_ax and shape[-2] % msize != 0:
            k_ax = None
        return pad([k_ax, n_ax], 2)

    if leafname == "input_scale":
        k_ax, _ = _linear_axes(parent, shape[-1], 0, mesh, cfg)
        return pad([k_ax if shape[-1] % msize == 0 else None], 1)

    if leafname == "b" and len(parts) >= 2:
        _, n_ax = _linear_axes(parent, 0, shape[-1], mesh, cfg)
        return pad([n_ax if shape[-1] % msize == 0 else None], 1)

    return P(*([None] * len(shape)))  # norms, scalars, conv, A_log, ...


def tag_tensor_parallel(params: Any, mesh: Mesh, cfg=None) -> Any:
    """Record on every `PackedLinear` how `param_pspec` splits it over the
    ``model`` axis of ``mesh`` (``tp="col"``: N, ``"row"``: K, None:
    replicated), so `core.qlinear` can run the fused kernel per shard
    under `shard_map` exactly as the weights are laid out."""
    import dataclasses

    from repro.core.packing import PackedLinear
    from repro.utils.tree import path_str

    def tag(path, node):
        if not isinstance(node, PackedLinear):
            return node
        spec = tuple(param_pspec(path_str(path) + "/qweight", node.qweight,
                                 mesh, cfg))
        k_ax, n_ax = ([None, None] + list(spec))[-2:]
        tp = "row" if k_ax == "model" else "col" if n_ax == "model" else None
        return dataclasses.replace(node, tp=tp)

    return jax.tree_util.tree_map_with_path(
        tag, params, is_leaf=lambda x: isinstance(x, PackedLinear))


def zero1_pspec(pspec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """ZeRO-1: additionally shard optimizer moments over the data axis.

    Picks the first dimension that is unsharded and divisible by |data| —
    on top of whatever TP sharding the param already has.
    """
    dsize = mesh.shape.get("data", 1)
    if dsize == 1:
        return pspec
    spec = list(pspec) + [None] * (len(shape) - len(pspec))
    for i, (ax, dim) in enumerate(zip(spec, shape)):
        if ax is None and dim % dsize == 0 and dim >= dsize:
            spec[i] = "data"
            return P(*spec)
    return P(*spec)


# ---------------------------------------------------------------------------
# Decode-cache sharding
# ---------------------------------------------------------------------------

def cache_pspec(path: str, leaf: Any, mesh: Mesh, cfg=None) -> P:
    """Sharding for KV/SSM decode caches.

    Layout per leaf (leading dim may be a stacked segment-layer dim):
      k/v      [L, B, S, H, hd] → batch on B; S over model (SP-decode) when
               divisible, else heads.
      ckv/kpe  [L, B, S, R]     → batch on B, S over model (MLA latent).
      conv_*   [L, B, d_conv, C] → batch on B, channels over model.
      state    [L, B, nh, hd, ds]→ batch on B, heads over model if divisible.
    """
    shape = tuple(leaf.shape)
    parts = path.split("/")
    leafname = parts[-1]
    msize = mesh.shape.get("model", 1)
    b_ax = "batch"

    def full(tail: list) -> P:
        lead = [None] * (len(shape) - len(tail))
        mesh_ready = _resolve(mesh, tuple(lead + tail), shape)
        return mesh_ready

    if leafname in ("k", "v"):
        s_dim, h_dim = shape[-3], shape[-2]
        if s_dim % msize == 0 and s_dim >= 8 * msize:
            return full([b_ax, "model", None, None])
        if h_dim % msize == 0:
            return full([b_ax, None, "model", None])
        return full([b_ax, None, None, None])
    if leafname in ("ks", "vs"):  # int8 KV-cache scales [.., B, S, H]
        s_dim = shape[-2]
        if s_dim % msize == 0 and s_dim >= 8 * msize:
            return full([b_ax, "model", None])
        return full([b_ax, None, None])
    if leafname in ("ckv", "kpe"):
        s_dim = shape[-2]
        if s_dim % msize == 0 and s_dim >= 8 * msize:
            return full([b_ax, "model", None])
        return full([b_ax, None, None])
    if leafname.startswith("conv"):
        return full([b_ax, None, "model"])
    if leafname == "state":
        return full([b_ax, "model", None, None])
    # fallback: batch on the second-to-last... be conservative: batch on dim
    # right after the stacked layer dim if it matches the global batch.
    return full([b_ax] + [None] * (len(shape) - (len(shape) - 1)))


# ---------------------------------------------------------------------------
# Serving page-pool sharding (tensor-parallel paged KV)
# ---------------------------------------------------------------------------

def paged_cache_pspec(path: str, leaf: Any, mesh: Mesh, cfg=None) -> P:
    """Sharding for the serving engine's paged decode cache.

    Page pools shard over **KV heads** on the ``model`` axis — page IDs
    index the (replicated) leading ``num_pages`` dim, so the host-side
    pager's free list / refcounts / page tables stay device-agnostic and
    a physical page is simply striped across the mesh:

      k/v pools   [L, N, Hkv, P, hd] → heads over ``model``
      ks/vs strips[L, N, Hkv, P]     → heads over ``model``
      ring k/v    [L, B, W, Hkv, hd] → heads over ``model``

    Bounded per-slot state (SSM states, MLA latents, conv tails) is
    replicated — its footprint is small by construction. Head counts that
    don't divide the axis fall back to replication here, but the serving
    engine refuses such meshes up front (a clear construction-time error
    beats a silently-replicated pool).
    """
    shape = tuple(leaf.shape)
    leafname = path.split("/")[-1]
    msize = mesh.shape.get("model", 1)
    # distance of the head axis from the end of the leaf's shape
    if "kv_pool" in path.split("/"):
        head = {"k": 3, "v": 3, "ks": 2, "vs": 2}.get(leafname)
    else:
        head = {"k": 2, "v": 2}.get(leafname)
    if head is None or len(shape) < head or shape[-head] % msize:
        return P(*([None] * len(shape)))
    spec = [None] * len(shape)
    spec[-head] = "model"
    return P(*spec)


def spill_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for page strips crossing the device↔host spill tier.

    Preemption gathers an evicted slot's pages out of the (head-sharded)
    pools as ``[L, n_pages, P, ...]`` strips and parks their bytes in
    host memory; restore scatters them back into freshly drawn pages.
    The strips leave the mesh **replicated**: the gather's out-sharding
    performs the per-device head-shard collection in the same dispatch
    (one all-gather over ``model`` for the strip, not the pool), so the
    host tier holds one complete device-agnostic copy — int8 codes plus
    scale strips when the pool is quantized, i.e. the spilled bytes stay
    int8-recompressed. On restore the scatter's in-sharding re-stripes
    the replicated strip back over KV heads via `paged_cache_pspec`, so
    each device writes only its head shard. Page IDs (the gather/scatter
    index operand) use the same replicated sharding.
    """
    return NamedSharding(mesh, P())


def handoff_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for KV page strips crossing ENGINES (disaggregated
    prefill → decode handoff, `serving.disagg`).

    Identical to `spill_sharding` by construction, and that identity is
    the load-bearing property of cross-mesh disaggregation: because the
    gather's out-sharding leaves the strip **replicated** (the all-gather
    over ``model`` happens inside the prefill engine's dispatch), the
    wire image carries no trace of the prefill mesh. A decode engine on a
    *different* mesh — more chips, fewer chips, or no mesh at all — feeds
    the same strip to its scatter, whose in-sharding re-stripes it over
    the decode mesh's KV-head axis via `paged_cache_pspec`. The handoff
    is therefore a reshard-on-adopt: no per-mesh-pair transfer code, and
    host page IDs stay device-agnostic on both sides.
    """
    return spill_sharding(mesh)


def serving_mesh(model: int | None = None, devices=None) -> Mesh:
    """A 1-D ``('model',)`` mesh over the first ``model`` of ``devices``.

    The serving engine's tensor-parallel axis: weights column/row-shard
    through `param_pspec`, page pools shard over KV heads through
    `paged_cache_pspec`, and everything host-visible (page tables, token
    blocks, sampled tokens) stays replicated. ``devices`` defaults to
    every local device; a fleet hands each replica its own disjoint slice
    (`launch.specs.FleetSpec.build`). ``model=None`` takes all of
    ``devices``; ``model=1`` is the degenerate mesh whose dispatches are
    identical to the unsharded path, placed on ``devices[0]``.
    """
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices) if model is None else model
    if n < 1 or n > len(devices):
        raise ValueError(f"serving_mesh(model={model}): have "
                         f"{len(devices)} devices")
    return Mesh(np.asarray(devices[:n]), ("model",))


# ---------------------------------------------------------------------------
# Tree helpers
# ---------------------------------------------------------------------------

def pspec_tree(tree: Any, mesh: Mesh, rule, cfg=None) -> Any:
    """Map ``rule(path, leaf, mesh, cfg) -> PartitionSpec`` over a pytree."""
    from repro.utils.tree import map_with_path
    return map_with_path(lambda p, x: rule(p, x, mesh, cfg), tree)


def make_sharding(tree: Any, mesh: Mesh, rule, cfg=None) -> Any:
    return jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                        pspec_tree(tree, mesh, rule, cfg),
                        is_leaf=lambda x: isinstance(x, P))

