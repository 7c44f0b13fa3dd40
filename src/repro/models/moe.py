"""Mixture-of-Experts: top-k routing with capacity-based scatter dispatch.

GShard-style algorithm (shardable under pure pjit):
  1. router logits → top-k (gates, expert ids) per token,
  2. position-in-expert via k sequential cumsums over the one-hot assignment
     (tokens beyond an expert's capacity are dropped — training-standard),
  3. scatter tokens into an ``[E, C, D]`` buffer (capacity sharded over the
     DP axes, expert FFN dim over `model` → the expert matmuls run without
     any collective),
  4. batched expert GLU via einsum over stacked ``[E, D, F]`` weights,
  5. gather back per (token, k) slot, combine with gate weights.

Qwen2-MoE specifics supported: 4 shared experts applied to every token with
a sigmoid gate, routed top-4 over 60 experts, optional top-k prob
normalization. DeepSeek-V2-lite reuses the same module (2 shared, top-6).

Expert linears are stacked ``[E, K, N]`` and quantize through the AWQ
pipeline like any other linear (per-expert groups — the stacked dim is just
extra leading layers to `quantize_params`). The tiny router stays FP
(AWQ convention; it is salience-critical and <0.01% of bytes).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packing import PackedLinear, dequantize_packed
from repro.models import layers
from repro.models.layers import activation, linear


def moe_init(key, cfg, dtype=jnp.float32):
    ks = jax.random.split(key, 8)
    d, e, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    scale = 1.0 / np.sqrt(d)

    def stacked(k_, a, b_, s):
        return {"w": (jax.random.normal(k_, (e, a, b_)) * s).astype(dtype)}

    p = {
        "router": layers.linear_init(ks[0], d, e, dtype=jnp.float32),
        "experts": {
            "gate": stacked(ks[1], d, f, scale),
            "up": stacked(ks[2], d, f, scale),
            "down": stacked(ks[3], f, d, 1.0 / np.sqrt(f)),
        },
    }
    if cfg.num_shared_experts:
        sf = cfg.shared_d_ff
        p["shared"] = {
            "gate": layers.linear_init(ks[4], d, sf, dtype=dtype),
            "up": layers.linear_init(ks[5], d, sf, dtype=dtype),
            "down": layers.linear_init(ks[6], sf, d, dtype=dtype),
        }
        if cfg.shared_expert_gate:
            p["shared_gate"] = layers.linear_init(ks[7], d, 1,
                                                  dtype=jnp.float32)
    return p


def _expert_weight(node, name: str) -> jax.Array:
    """[E, K, N] float weights — EAGER all-expert dequant if packed.

    Only the data-parallel float dispatch still uses this (it replicates
    float weights into the manual region). The packed hot paths go
    through `_glu_ffn_packed` / the per-expert maps in `body_q`, which
    keep one expert's dense weight live at a time.
    """
    leaf = node[name]
    if isinstance(leaf, PackedLinear):
        e = leaf.qweight.shape[0]
        w = jax.vmap(lambda q, s, z, isc: dequantize_packed(
            PackedLinear(q, s, z, isc, None, leaf.group_size), jnp.float32)
            * isc[:, None])(leaf.qweight, leaf.scales, leaf.zeros,
                            leaf.input_scale)
        return w
    return leaf["w"]


def _dequant_block(q, s, z):
    """ONE [K//PACK, N] packed block + [G, N] meta → [K, N] f32."""
    from repro.core.packing import unpack_int4
    qi = unpack_int4(q).astype(jnp.float32)           # [K, N]
    g, n = s.shape
    qg = qi.reshape(g, qi.shape[0] // g, n)
    w = (qg - z[:, None, :].astype(jnp.float32)) \
        * s[:, None, :].astype(jnp.float32)
    return w.reshape(qi.shape[0], n)


def _glu_ffn(buf, wg, wu, wd, act):
    """Batched expert GLU over the [E, C, D] capacity buffer (float)."""
    h = jnp.einsum("ecd,edf->ecf", buf, wg.astype(buf.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(buf.dtype))
    h = activation(act, h) * u
    return jnp.einsum("ecf,efd->ecd", h, wd.astype(buf.dtype))


def _glu_ffn_packed(experts, buf, act):
    """Expert GLU with PER-EXPERT lazy dequant.

    ``lax.map`` (a sequential scan) dequantizes one expert inside each
    iteration, so peak live weight bytes are ONE expert's dense [K, N] —
    not the full [E, K, N] stack the eager path materialized, which
    erased the W4 bandwidth win exactly on the decode hot path.
    """
    pg, pu, pd = experts["gate"], experts["up"], experts["down"]

    def one(args):
        b, g_, u_, d_ = args
        wg = _dequant_block(*g_[:3]) * g_[3][:, None]
        wu = _dequant_block(*u_[:3]) * u_[3][:, None]
        wd = _dequant_block(*d_[:3]) * d_[3][:, None]
        h = activation(act, b @ wg.astype(b.dtype)) * (b @ wu.astype(b.dtype))
        return h @ wd.astype(b.dtype)

    def leaves(pl):
        return (pl.qweight, pl.scales, pl.zeros, pl.input_scale)

    return jax.lax.map(one, (buf, leaves(pg), leaves(pu), leaves(pd)))


def capacity(cfg, n_tokens: int) -> int:
    # Small batches (decode / short prefill) run DROPLESS: per-expert load is
    # bounded by n_tokens (top-k indices are distinct), so cap = T suffices —
    # serving never silently drops tokens. Large training batches use the
    # standard capacity-factor formula (GShard dropping).
    if n_tokens <= 1024:
        return n_tokens
    c = int(np.ceil(cfg.top_k * n_tokens / cfg.num_experts
                    * cfg.capacity_factor))
    return max(8, int(np.ceil(c / 8)) * 8)


def _dp_groups(n_tokens: int) -> int:
    """Dispatch group count = DP degree (gcd'd against the token count).

    Grouped dispatch is what keeps the GShard algorithm SPMD-local: tokens
    are reshaped [G, T/G, D] with G sharded over the DP axes, so the
    one-hot/cumsum/scatter machinery runs independently per data shard —
    no cross-shard replication of the expert buffer (the naive global-
    capacity formulation made XLA replicate a [E, C, D] buffer per device).
    """
    from repro.distributed.sharding import batch_axes, current_mesh
    import math
    mesh = current_mesh()
    if mesh is None:
        return 1
    dp = 1
    for a in batch_axes(mesh):
        dp *= mesh.shape[a]
    return math.gcd(n_tokens, dp)


def _dispatch_compute_combine(xt, idx, gates, ffn, cfg, cap):
    """Scatter → expert FFN (``ffn(buf) -> out_buf``) → gather, LOCAL.

    xt [T, D] (local tokens), idx/gates [T, k]. ``ffn`` maps the
    [E, C, D] capacity buffer to [E, C, D'] — `_glu_ffn` for float
    stacks, `_glu_ffn_packed` for lazy per-expert dequant. Pure local
    computation — no collective ops; designed to run inside `shard_map`.
    """
    t, d = xt.shape
    e, k = cfg.num_experts, cfg.top_k
    buf = jnp.zeros((e, cap, d), xt.dtype)
    counts = jnp.zeros((e,), jnp.int32)
    slot_list, keep_list = [], []
    for j in range(k):
        onehot = jax.nn.one_hot(idx[:, j], e, dtype=jnp.int32)    # [T, E]
        pos_in = jnp.cumsum(onehot, axis=0) - onehot
        slot = jnp.take_along_axis(pos_in, idx[:, j:j + 1],
                                   axis=1)[:, 0] + counts[idx[:, j]]
        keep = slot < cap
        slot = jnp.where(keep, slot, cap - 1)
        buf = buf.at[idx[:, j], slot].add(
            jnp.where(keep[:, None], xt, 0), mode="drop")
        counts = counts + jnp.sum(onehot, axis=0)
        slot_list.append(slot)
        keep_list.append(keep)

    out_buf = ffn(buf)

    y = jnp.zeros_like(xt)
    for j in range(k):
        got = out_buf[idx[:, j], slot_list[j]]                    # [T, D]
        y += jnp.where(keep_list[j][:, None], got, 0) \
            * gates[:, j:j + 1].astype(xt.dtype)
    return y


def moe_apply(p, x: jax.Array, cfg, name=None) -> tuple[jax.Array, jax.Array]:
    """x [B, S, D] (or [T, D]) → (y, aux_loss).

    Distribution (§Perf B2, DESIGN §5): the dispatch/combine runs MANUALLY
    per device via `shard_map` — tokens stay on their DP shard, the expert
    FFN dim is TP-sharded over `model`, and the only collective is one
    explicit psum of the token outputs over `model` (the row-parallel
    partial sum). Under auto-SPMD the data-dependent scatter/gather made
    XLA shard the scatter updates and all-reduce the full [E, C, D] buffer
    per layer (~100 GB/chip/step on deepseek-v2 train_4k — the dominant
    §Roofline term before this change, 29× over the DP-gradient floor).
    """
    from repro.distributed.sharding import batch_axes, current_mesh
    nm = (lambda s: None) if name is None else name
    lead = x.shape[:-1]
    d = x.shape[-1]
    xt = x.reshape(-1, d)                         # [T, D] global tokens
    t = xt.shape[0]
    e, k = cfg.num_experts, cfg.top_k

    logits = linear(p["router"], xt.astype(jnp.float32))      # [T, E] fp32
    probs = jax.nn.softmax(logits, axis=-1)
    gates_t, idx_t = jax.lax.top_k(probs, k)                  # [T, k]
    if cfg.norm_topk_prob:
        gates_t = gates_t / jnp.clip(jnp.sum(gates_t, -1, keepdims=True),
                                     1e-9)

    packed = isinstance(p["experts"]["gate"], PackedLinear)
    mesh = current_mesh()
    dp_size = 1
    if mesh is not None:
        for a in batch_axes(mesh):
            dp_size *= mesh.shape[a]
    # manual dispatch requires one whole token-group per DP shard
    if mesh is not None and dp_size > 1 and t % dp_size == 0:
        from jax.sharding import PartitionSpec as P
        dp = batch_axes(mesh)
        g = dp_size
        tg = t // g
        cap = capacity(cfg, tg)
        xg = xt.reshape(g, tg, d)
        idx = idx_t.reshape(g, tg, k)
        gates = gates_t.reshape(g, tg, k)
        has_model = "model" in mesh.axis_names

        if packed and has_model:
            # §Perf B4 (quantized serving): expert weights enter the manual
            # region PACKED — gate/up F-sharded, down D-sharded (F-sharding
            # would split quant groups; see sharding.py) — and dequantize
            # shard-locally. Comm: all-gather of h over F and of y over D,
            # both tiny at decode token counts. No weight ever crosses ICI.
            pg, pu, pd = (p["experts"][n] for n in ("gate", "up", "down"))

            def body_q(xg_l, idx_l, gates_l, qg, sg, zg, isg, qu, su, zu,
                       isu, qd, sd, zd, isd):
                xt_l, idx_ll, gates_ll = xg_l[0], idx_l[0], gates_l[0]
                e = cfg.num_experts
                buf = jnp.zeros((e, cap, d), xt_l.dtype)
                counts = jnp.zeros((e,), jnp.int32)
                slots, keeps = [], []
                for j in range(k):
                    onehot = jax.nn.one_hot(idx_ll[:, j], e,
                                            dtype=jnp.int32)
                    pos_in = jnp.cumsum(onehot, axis=0) - onehot
                    slot = jnp.take_along_axis(
                        pos_in, idx_ll[:, j:j + 1], axis=1)[:, 0] \
                        + counts[idx_ll[:, j]]
                    keep = slot < cap
                    slot = jnp.where(keep, slot, cap - 1)
                    buf = buf.at[idx_ll[:, j], slot].add(
                        jnp.where(keep[:, None], xt_l, 0), mode="drop")
                    counts = counts + jnp.sum(onehot, axis=0)
                    slots.append(slot)
                    keeps.append(keep)
                # Per-expert lazy dequant: effective weight =
                # diag(input_scale) @ dequant(qweight), one LOCAL expert
                # shard live at a time (lax.map = sequential scan).
                def gateup_one(args):
                    b, g_, u_ = args
                    wg_e = _dequant_block(*g_[:3]) * g_[3][:, None]
                    wu_e = _dequant_block(*u_[:3]) * u_[3][:, None]
                    return activation(cfg.act, b @ wg_e.astype(b.dtype)) \
                        * (b @ wu_e.astype(b.dtype))
                h = jax.lax.map(gateup_one,
                                (buf, (qg, sg, zg, isg),
                                 (qu, su, zu, isu)))          # [E,C,F/m]
                h = jax.lax.all_gather(h, "model", axis=2, tiled=True)

                def down_one(args):
                    hh, d_ = args
                    wd_e = _dequant_block(*d_[:3]) * d_[3][:, None]
                    return hh @ wd_e.astype(hh.dtype)
                out_buf = jax.lax.map(down_one,
                                      (h, (qd, sd, zd, isd)))  # [E,C,D/m]
                y_l = jnp.zeros((tg, out_buf.shape[-1]), xt_l.dtype)
                for j in range(k):
                    got = out_buf[idx_ll[:, j], slots[j]]
                    y_l += jnp.where(keeps[j][:, None], got, 0) \
                        * gates_ll[:, j:j + 1].astype(xt_l.dtype)
                y_l = jax.lax.all_gather(y_l, "model", axis=1, tiled=True)
                return y_l[None]

            wsp = P(None, None, "model")
            y = jax.shard_map(
                body_q, mesh=mesh,
                in_specs=(P(dp), P(dp), P(dp),
                          wsp, wsp, wsp, P(),
                          wsp, wsp, wsp, P(),
                          wsp, wsp, wsp, P()),
                out_specs=P(dp),
                check_vma=False,  # all_gather'd y IS replicated over model
            )(xg, idx, gates,
              pg.qweight, pg.scales, pg.zeros, pg.input_scale,
              pu.qweight, pu.scales, pu.zeros, pu.input_scale,
              pd.qweight, pd.scales, pd.zeros, pd.input_scale)
            y = y.reshape(t, d)
        else:
            wg = _expert_weight(p["experts"], "gate")
            wu = _expert_weight(p["experts"], "up")
            wd = _expert_weight(p["experts"], "down")

            def body(xg_l, idx_l, gates_l, wg_l, wu_l, wd_l):
                y_l = _dispatch_compute_combine(
                    xg_l[0], idx_l[0], gates_l[0],
                    lambda b: _glu_ffn(b, wg_l, wu_l, wd_l, cfg.act),
                    cfg, cap)
                if has_model:
                    y_l = jax.lax.psum(y_l, "model")  # row-parallel psum
                return y_l[None]

            wspec = P(None, None, "model") if has_model else P()
            wspec_d = P(None, "model", None) if has_model else P()
            y = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(dp), P(dp), P(dp), wspec, wspec, wspec_d),
                out_specs=P(dp),
            )(xg, idx, gates, wg, wu, wd)
            y = y.reshape(t, d)
    else:
        cap = capacity(cfg, t)
        if packed:
            ffn = lambda b: _glu_ffn_packed(p["experts"], b, cfg.act)  # noqa: E731
        else:
            wg = _expert_weight(p["experts"], "gate")
            wu = _expert_weight(p["experts"], "up")
            wd = _expert_weight(p["experts"], "down")
            ffn = lambda b: _glu_ffn(b, wg, wu, wd, cfg.act)  # noqa: E731
        y = _dispatch_compute_combine(xt, idx_t, gates_t, ffn, cfg, cap)

    # shared experts (dense path over every token)
    if "shared" in p:
        sh = p["shared"]
        g = activation(cfg.act, linear(sh["gate"], xt, nm("shared/gate")))
        u2 = linear(sh["up"], xt, nm("shared/up"))
        s_out = linear(sh["down"], g * u2, nm("shared/down"))
        if "shared_gate" in p:
            sg = jax.nn.sigmoid(linear(p["shared_gate"],
                                       xt.astype(jnp.float32)))
            s_out = s_out * sg.astype(s_out.dtype)
        y = y + s_out

    # Switch-style load-balance aux loss
    me = jnp.mean(probs, axis=0)                                 # [E]
    ce = jnp.mean(jax.nn.one_hot(idx_t[:, 0], e, dtype=jnp.float32), axis=0)
    aux = cfg.router_aux_weight * e * jnp.sum(me * ce)
    return y.reshape(*lead, d), aux
