"""ShapeDtypeStruct stand-ins for every (arch × shape-cell) input.

Nothing here allocates: params/optimizer/caches/batches are all
`jax.eval_shape`-derived structs with NamedShardings attached, which is what
lets the dry-run lower+compile 9B-param models on a CPU container
(DESIGN.md §7).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import ShapeCell
from repro.configs.base import ModelConfig
from repro.core.awq import AWQConfig
from repro.core.pipeline import quantize_params
from repro.core.quantize import QuantConfig
from repro.distributed import sharding as shd
from repro.models import build_model
from repro.training.train_step import init_train_state


def _sds(tree: Any, shardings: Any) -> Any:
    """Attach shardings to a ShapeDtypeStruct tree."""
    return jax.tree.map(
        lambda t, s: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=s),
        tree, shardings)


def batch_specs(cfg: ModelConfig, cell: ShapeCell, mesh: Mesh) -> dict:
    """Global-batch input ShapeDtypeStructs for one shape cell."""
    b, s = cell.global_batch, cell.seq_len
    bp = NamedSharding(mesh, shd._resolve(mesh, ("batch", None), (b, s)))
    out: dict = {}
    if cfg.frontend == "audio":
        fshape = (b, s, cfg.frontend_dim)
        fsh = NamedSharding(mesh, shd._resolve(mesh, ("batch", None, None),
                                               fshape))
        out["features"] = jax.ShapeDtypeStruct(fshape, jnp.float32,
                                               sharding=fsh)
    else:
        if cfg.frontend == "vision":
            s_text = s - cfg.num_patches  # image span + text = cell seq_len
            ishape = (b, cfg.num_patches, cfg.frontend_dim)
            ish = NamedSharding(mesh, shd._resolve(
                mesh, ("batch", None, None), ishape))
            out["images"] = jax.ShapeDtypeStruct(ishape, jnp.float32,
                                                 sharding=ish)
        else:
            s_text = s
        tp = NamedSharding(mesh, shd._resolve(mesh, ("batch", None),
                                              (b, s_text)))
        out["tokens"] = jax.ShapeDtypeStruct((b, s_text), jnp.int32,
                                             sharding=tp)
    if cell.step == "train" or cfg.is_encoder:
        lshape = (b, s if cfg.frontend != "vision" else s_text)
        lsh = NamedSharding(mesh, shd._resolve(mesh, ("batch", None), lshape))
        out["labels"] = jax.ShapeDtypeStruct(lshape, jnp.int32, sharding=lsh)
    if cell.step != "train":
        out.pop("labels", None)
    return out


def param_specs(cfg: ModelConfig, mesh: Mesh, quant: bool) -> Any:
    model = build_model(cfg)
    p_shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    if quant:
        qcfg = AWQConfig(quant=QuantConfig(group_size=64))
        p_shapes = jax.eval_shape(
            lambda: quantize_params(
                jax.tree.map(lambda t: jnp.zeros(t.shape, t.dtype),
                             p_shapes), None, qcfg)[0])
    shardings = shd.make_sharding(p_shapes, mesh, shd.param_pspec, cfg)
    return _sds(p_shapes, shardings)


def train_state_specs(cfg: ModelConfig, mesh: Mesh) -> Any:
    model = build_model(cfg)
    st = jax.eval_shape(
        lambda: init_train_state(model, jax.random.PRNGKey(0)))
    p_sh = shd.pspec_tree(st["params"], mesh, shd.param_pspec, cfg)
    m_sh = jax.tree.map(
        lambda spec, leaf: shd.zero1_pspec(spec, leaf.shape, mesh),
        p_sh, st["params"], is_leaf=lambda x: isinstance(x, P))
    specs = {"params": p_sh, "opt": {"m": m_sh, "v": m_sh}, "step": P()}
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    return _sds(st, shardings), shardings


def cache_specs(cfg: ModelConfig, mesh: Mesh, batch: int,
                max_seq: int) -> Any:
    model = build_model(cfg)
    c_shapes = jax.eval_shape(
        lambda: model.init_cache(batch, max_seq))
    shardings = shd.make_sharding(c_shapes, mesh, shd.cache_pspec, cfg)
    return _sds(c_shapes, shardings)


def decode_token_specs(mesh: Mesh, batch: int) -> tuple:
    sh = NamedSharding(mesh, shd._resolve(mesh, ("batch",), (batch,)))
    tok = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=sh)
    pos = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=sh)
    return tok, pos


# ---------------------------------------------------------------------------
# Serving fleet specs (k8s-style declarative deployment description)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ReplicaSpec:
    """One serving replica, declaratively.

    ``mesh_axis`` is the replica's tensor-parallel ``model``-axis width
    (1 = unsharded). ``disagg=True`` serves the replica as a
    `DisaggController` prefill/decode pair with per-side mesh widths
    instead of one `GenerationEngine`; the prefill side takes the first
    ``prefill_mesh_axis`` of the replica's devices and the decode side
    the next ``decode_mesh_axis``, so the two never overlap.
    ``engine_kwargs`` forward verbatim to the engine constructor(s) —
    shape, KV quant, speculation, preemption knobs.
    """
    mesh_axis: int = 1
    disagg: bool = False
    prefill_mesh_axis: int = 1
    decode_mesh_axis: int = 1
    engine_kwargs: dict = dataclasses.field(default_factory=dict)

    @property
    def devices_needed(self) -> int:
        """Devices one replica occupies."""
        if self.disagg:
            return self.prefill_mesh_axis + self.decode_mesh_axis
        return self.mesh_axis

    def build(self, model, params, devices=None, **overrides):
        """Construct the replica this spec describes on ``devices`` (its
        own slice of the host; ``None`` = the default device, with
        ``jax.devices()`` prefixes for meshes wider than one)."""
        from repro.distributed import serving_mesh
        from repro.serving import DisaggController, GenerationEngine
        kw = {**self.engine_kwargs, **overrides}

        def mesh(width, offset):
            if devices is None:
                return serving_mesh(width) if width > 1 else None
            devs = devices[offset:offset + width]
            # one device that is the default one needs no mesh: the
            # replica keeps the unsharded engine, which also serves archs
            # a mesh cannot (one-shot prefill, SSM/ring state)
            if width == 1 and devs[0] == jax.devices()[0]:
                return None
            return serving_mesh(width, devs)

        if self.disagg:
            return DisaggController(
                model, params,
                prefill_mesh=mesh(self.prefill_mesh_axis, 0),
                decode_mesh=mesh(self.decode_mesh_axis,
                                 self.prefill_mesh_axis),
                **kw)
        return GenerationEngine(model, params, mesh=mesh(self.mesh_axis, 0),
                                **kw)


@dataclasses.dataclass(frozen=True)
class FleetSpec:
    """A whole serving fleet, declaratively: N replicas of a
    `ReplicaSpec` behind a `serving.Router`.

    The analog of a k8s Deployment + Service: ``replicas`` is the scale,
    ``replica`` the pod template, ``drain_timeout_s`` bounds how long
    `drain_replica` may step the fleet before giving up (elastic
    scale-down), and the placement knobs configure the router's scoring
    (see `serving.router.Router`). `build` materializes the fleet;
    `repro.launch.serve --replicas N` and `examples/serve_fleet.py`
    drive it.
    """
    replicas: int = 1
    replica: ReplicaSpec = dataclasses.field(default_factory=ReplicaSpec)
    drain_timeout_s: float = 30.0
    placement: str = "affinity"
    affinity_threshold: int = 1
    warmup: bool = False

    def build(self, model, params, **overrides):
        """Materialize the fleet: build every replica, wrap the router,
        optionally precompile each replica's dispatch family.

        Replica ``i`` takes devices ``[i·n, (i+1)·n)`` of ``jax.devices()``
        (``n`` = `ReplicaSpec.devices_needed`), so replicas never share a
        chip. A host with fewer devices than one-device replicas (a CPU
        host) keeps them all on the default device; wider replicas that
        do not fit are refused.
        """
        from repro.serving import Router
        if self.replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {self.replicas}")
        devs = jax.devices()
        need = self.replica.devices_needed
        if self.replicas * need <= len(devs):
            slices = [devs[i * need:(i + 1) * need]
                      for i in range(self.replicas)]
        elif need == 1:
            slices = [None] * self.replicas
        else:
            raise ValueError(
                f"{self.replicas} replicas x {need} devices do not fit on "
                f"{len(devs)} devices")
        fleet = [self.replica.build(model, params, devices=d, **overrides)
                 for d in slices]
        router = Router(fleet, placement=self.placement,
                        affinity_threshold=self.affinity_threshold)
        if self.warmup:
            router.warmup()
        return router
