"""Serving facade: static-batch generation + continuous-batching streaming.

This is the paper's end-to-end inference flow (§III: model packed offline,
streamed to the accelerator, decoded token-by-token) grown into a serving
subsystem:

  * `GenerationEngine(model, params)` — params may be float or AWQ-packed
    (`core.pipeline.quantize_params` output). Every linear dispatches
    through `qlinear_apply`, but a quantized swap is NOT engine-invisible;
    the engine does two things to make it work: (a) every compiled
    dispatch is keyed on the active `ExecutionConfig` (`qlinear_apply`
    reads it at trace time, so without the keying a
    `set_execution_config(...)` after the first step would be silently
    ignored — flipping impl now retraces on the next step), and (b) under
    a mesh the `PackedLinear` leaves (qweight/scales/zeros/input_scale)
    shard through the same `param_pspec` rules as the float weight they
    replace, keeping whole quant groups per device.
  * static batch — `generate` (host loop, EOS early-exit) and
    `generate_scan` (fixed-length `lax.scan`, the throughput-benchmark
    path). These are the baselines the serving benchmarks compare against.
  * streaming — `submit()` / `step()` / `collect()` on top of
    `serving.scheduler` (continuous batching) and `serving.kv_pager`
    (paged KV cache): per-request sampling params, EOS eviction with
    immediate slot backfill, one fixed-shape jit'd dispatch per step
    regardless of batch composition.
  * chunked prefill — on pure paged-attention archs every step is ONE
    token-budget dispatch of ``num_slots × prefill_chunk`` positions
    that packs prefill chunks and decode tokens from mixed requests
    (`Model.chunk_step`); prompts are fed in fixed-size chunks whose KV
    scatters straight into the page pools, the first token is sampled
    when the last chunk lands, and the compiled family is bounded at
    O(log) context buckets × two block widths (no jit-per-prompt-length
    family). Archs with bounded
    sequential per-slot state (rings / SSM / MLA) keep the one-shot
    prefill path (``chunked_prefill=False`` forces it everywhere — the
    identity baseline).
  * memory levers — ``kv_quant="int8"`` stores the page pools as int8
    codes + per-(position, head) scale strips (quantize-on-commit,
    dequant fused into the paged attention read; ~1.9× more resident
    tokens per byte), and ``submit(..., prefix_id=...)`` aliases a shared
    system prompt's full pages across requests (refcounted, COW tail).
    Under chunked prefill the aliased tokens are also **never
    recomputed** (the chunk attends over the already-committed pages), so
    sharing saves prefill FLOPs too; `pin_prefix()` keeps a hot prefix
    resident across bursts.
  * speculative decoding — ``spec_decode="ngram"`` (prompt-lookup
    self-drafting, no second model) or ``"draft_model"`` (a small greedy
    drafter with its own dense cache) proposes up to ``spec_k`` tokens
    per decoding slot; the unified chunk dispatch verifies them in ONE
    weight pass (a verify run is just a multi-token decode row), a
    device-side acceptance sampler keeps outputs distribution-faithful
    (token-identical to sequential decode under greedy), and rejected
    suffixes roll the paged KV back via `KVPager.truncate`. One weight
    stream now amortizes over up to ``spec_k + 1`` emitted tokens — the
    lever the paper's 5.1 tok/s memory-bandwidth ceiling asks for.
    ``spec_adaptive=True`` lets the scheduler walk ``spec_k`` through
    ``{1, 2, 4, …, spec_k}`` from an EMA of the measured acceptance.
  * tensor parallelism — ``GenerationEngine(mesh=...)`` serves a
    TP-sharded model with TP-sharded paged KV over the mesh's ``model``
    axis: weights shard by the `distributed.sharding.param_pspec` rules,
    page pools stripe over KV heads (`paged_cache_pspec`), and every
    chunk/decode/verify dispatch is jit'd with explicit in/out shardings
    (page tables, token blocks and sampled tokens replicated). Page IDs
    are device-agnostic, so the host-side pager and scheduler are
    untouched by construction — admission, eviction, prefix sharing and
    rollback run identically, and greedy sharded streams are
    token-identical to the single-device engine.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.kv_pager import (KVPager, PagerConfig, PagerStats,
                                    commit_prefill)
from repro.serving.scheduler import Request, Scheduler
from repro.serving.tracing import StepTimes, span


@dataclasses.dataclass(frozen=True)
class SamplerConfig:
    temperature: float = 0.0    # 0 ⇒ greedy
    top_k: int = 0              # 0 ⇒ full softmax


@dataclasses.dataclass(frozen=True)
class EngineStats:
    """One structured serving snapshot — the public metrics surface.

    Everything the benchmarks (and an operator dashboard) need in one
    read: pager occupancy, dispatch/packing accounting, speculative
    acceptance, and the memory footprint of the page pools — global and
    **per device** (under a ``model`` mesh the pools stripe over KV
    heads, so the per-device number shrinks ~linearly with the axis).
    """
    pager: PagerStats
    # dispatch / packing
    dispatches: int               # unified steps issued
    prefill_tokens: int           # prompt tokens run through the model
    prefill_tokens_skipped: int   # aliased prompt tokens never re-run
    prefix_shared_pages: int      # pages aliased instead of allocated
    padding_waste: float          # padding / dispatched positions
    # speculative decoding
    acceptance_rate: float
    spec_tokens_per_row: float
    draft_tokens: int
    accepted_tokens: int
    rollbacks: int
    spec_k_now: int               # current draft length (adaptive)
    spec_fanout_now: int          # current tree root fanout (1 = linear)
    # SLO preemption / host KV tier
    preemptions: int              # slots spilled to the host tier
    pressure_spills: int          # spills by optimistic-admission pressure
    restores: int                 # parked requests re-admitted
    spilled_pages: int            # cumulative page strips gathered to host
    restored_pages: int           # cumulative page strips scattered back
    pages_spilled_now: int        # live host-tier pages right now
    restore_ms_mean: float        # mean wall latency of one restore
    # sharding + memory
    model_axis: int               # |model| mesh axis (1 = unsharded)
    kv_pool_bytes: int            # global page-pool footprint, all layers
    kv_pool_bytes_per_device: int
    kv_bytes_per_token: float
    # weight stream (the AWQ lever): resident bytes of the served params
    # (PackedLinear leaves count int4 packing + metadata) and the bytes
    # streamed per EMITTED token — one full weight pass per decode step,
    # amortized over spec-accepted tokens per row when speculating.
    weight_bytes: int
    weight_bytes_per_token: float
    # host spans and per-step counters of the serving step (`StepTimes`)
    step_times: StepTimes
    # load snapshot (cheap, host-only): what a fleet router needs to score
    # this engine as a placement target without touching scheduler/pager
    # internals. `queue_depth` counts requests waiting for a slot (queued
    # + preempted/parked); `admission_headroom` is the free pages an
    # admission can still draw (free minus standing reservations).
    queue_depth: int = 0
    admission_headroom: int = 0


def _tree_walk_greedy(g, tokens, parents, n_draft, depth):
    """Device-side greedy tree acceptance: from the root (in-row index 0),
    follow the child whose token equals the target argmax at the current
    node, as deep as the matches go.

    g ``[B, R]`` — the target argmax after each in-row position; tokens /
    parents ``[B, C]`` (parent = in-row index, ``-1`` = none); n_draft
    ``[B]`` node counts (nodes sit at in-row indices ``1 … n_draft``).
    Returns ``(fix [B], n_acc [B], path [B, depth])`` — the corrected /
    bonus token (argmax at the deepest accepted node), the accepted
    depth, and the accepted branch's in-row indices (0-padded). Emitting
    ``path`` tokens then ``fix`` reproduces sequential greedy decode
    token-for-token — the tree-speculation identity guarantee.
    """
    b, c = tokens.shape
    idx = jnp.arange(c, dtype=jnp.int32)[None, :]
    rmax = g.shape[1] - 1

    def body(t, carry):
        cur, n_acc, path, alive = carry
        g_cur = jnp.take_along_axis(g, jnp.clip(cur, 0, rmax)[:, None],
                                    axis=1)[:, 0]
        cand = ((parents == cur[:, None]) & (tokens == g_cur[:, None])
                & (idx >= 1) & (idx <= n_draft[:, None]) & alive[:, None])
        has = cand.any(axis=1)
        child = jnp.argmax(cand, axis=1).astype(jnp.int32)
        cur = jnp.where(has, child, cur)
        n_acc = n_acc + has.astype(jnp.int32)
        path = path.at[:, t].set(jnp.where(has, child, 0))
        return cur, n_acc, path, alive & has

    carry = (jnp.zeros(b, jnp.int32), jnp.zeros(b, jnp.int32),
             jnp.zeros((b, depth), jnp.int32), jnp.ones(b, bool))
    cur, n_acc, path, _ = jax.lax.fori_loop(0, depth, body, carry)
    fix = jnp.take_along_axis(g, jnp.clip(cur, 0, rmax)[:, None],
                              axis=1)[:, 0]
    return fix, n_acc, path


def _tree_walk_sampled(probs, tokens, parents, n_draft, depth, key):
    """Multi-branch acceptance sampling over a token tree (SpecInfer-style
    point-mass residuals), distribution-faithful per row.

    At each accepted node the children are tried in in-row order: child
    token x is accepted with probability ``p(x) / mass`` where ``p`` is
    the target distribution at the node and ``mass`` the residual left by
    previously rejected siblings (whose point mass is zeroed — standard
    residual acceptance, so the emitted marginal equals sequential
    sampling). When every child is rejected the fix token is drawn from
    the residual; at a leaf (or full depth) from the plain target — the
    bonus draw. ``probs [B, R, V]`` must already be temperature / top-k
    filtered; one-hot rows reduce exactly to `_tree_walk_greedy`.
    """
    b, c = tokens.shape
    v = probs.shape[-1]
    rmax = probs.shape[1] - 1
    ku, kf = jax.random.split(key)
    us = jax.random.uniform(ku, (depth, c, b))
    bidx = jnp.arange(b)

    def take_p(cur):
        return jnp.take_along_axis(
            probs, jnp.clip(cur, 0, rmax)[:, None, None], axis=1)[:, 0]

    def outer(t, carry):
        cur, n_acc, path, alive, p_bonus = carry
        u_t = jax.lax.dynamic_index_in_dim(us, t, 0, keepdims=False)

        def inner(j, ic):
            accepted, child, p_res = ic
            par_j = jax.lax.dynamic_index_in_dim(parents, j, 1,
                                                 keepdims=False)
            tok_j = jax.lax.dynamic_index_in_dim(tokens, j, 1,
                                                 keepdims=False)
            u_j = jax.lax.dynamic_index_in_dim(u_t, j, 0, keepdims=False)
            is_cand = (alive & ~accepted & (par_j == cur)
                       & (j <= n_draft))
            p_tok = p_res[bidx, tok_j]
            mass = p_res.sum(axis=1)
            acc = is_cand & (u_j * mass < p_tok)       # P = p_tok / mass
            rej = is_cand & ~acc
            p_res = p_res.at[bidx, tok_j].set(
                jnp.where(rej, 0.0, p_tok))
            return accepted | acc, jnp.where(acc, j, child), p_res

        accepted, child, p_res = jax.lax.fori_loop(
            1, c, inner,
            (jnp.zeros(b, bool), jnp.zeros(b, jnp.int32), take_p(cur)))
        stepped = alive & accepted
        p_bonus = jnp.where((alive & ~accepted)[:, None], p_res, p_bonus)
        cur = jnp.where(stepped, child, cur)
        n_acc = n_acc + stepped.astype(jnp.int32)
        path = path.at[:, t].set(jnp.where(stepped, child, 0))
        return cur, n_acc, path, stepped, p_bonus

    carry = (jnp.zeros(b, jnp.int32), jnp.zeros(b, jnp.int32),
             jnp.zeros((b, depth), jnp.int32), jnp.ones(b, bool),
             jnp.zeros((b, v), probs.dtype))
    cur, n_acc, path, alive, p_bonus = jax.lax.fori_loop(0, depth, outer,
                                                         carry)
    p_bonus = jnp.where(alive[:, None], take_p(cur), p_bonus)
    safe = jnp.where(p_bonus.sum(axis=1, keepdims=True) > 0, p_bonus, 1.0)
    fix = jax.random.categorical(kf, jnp.log(safe)).astype(jnp.int32)
    return fix, n_acc, path


def sample(logits: jax.Array, cfg: SamplerConfig, key) -> jax.Array:
    """logits [B, V] → token [B]."""
    if cfg.temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / cfg.temperature
    if cfg.top_k:
        kth = jax.lax.top_k(logits, cfg.top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -1e30, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


def sample_batched(logits: jax.Array, temps: jax.Array, topks: jax.Array,
                   key) -> jax.Array:
    """Per-row sampling params: logits [B, V], temps [B], topks [B] → [B].

    Rows with ``temps == 0`` are greedy (bitwise-identical to `sample` with
    temperature 0, which the continuous-vs-static identity tests rely on);
    ``topks == 0`` disables the top-k filter for that row.
    """
    v = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.where(temps > 0, temps, 1.0)[:, None]
    desc = -jnp.sort(-scaled, axis=-1)
    kth = jnp.take_along_axis(desc, jnp.clip(topks - 1, 0, v - 1)[:, None],
                              axis=1)
    filtered = jnp.where(scaled < kth, -1e30, scaled)
    scaled = jnp.where((topks > 0)[:, None], filtered, scaled)
    sampled = jax.random.categorical(key, scaled).astype(jnp.int32)
    return jnp.where(temps == 0.0, greedy, sampled)


class GenerationEngine:
    def __init__(self, model, params, *, max_seq: int | None = None,
                 sampler: SamplerConfig = SamplerConfig(),
                 eos_id: int = -1, donate_cache: bool = True,
                 num_slots: int = 4, page_size: int = 16,
                 num_pages: int | None = None, seed: int = 0,
                 kv_quant: str | None = None,
                 prefill_chunk: int = 16,
                 chunked_prefill: bool | None = None,
                 spec_decode: str | None = None,
                 spec_k: int = 4,
                 spec_ngram_max: int = 3,
                 spec_adaptive: bool = False,
                 spec_tree: bool = False,
                 spec_tree_fanout: int = 2,
                 draft_model=None, draft_params=None,
                 draft_fn=None,
                 mesh=None,
                 preemption: bool = False,
                 admission: str = "reserved"):
        self.model = model
        self.params = params
        self.cfg = model.cfg
        # tensor-parallel serving: a jax Mesh with a `model` axis. Weights
        # shard per param_pspec, page pools stripe over KV heads per
        # paged_cache_pspec, host-side pager/scheduler stay replicated
        # single-authority. Indivisible head counts fail HERE, not inside
        # a kernel three layers down.
        self._mesh = mesh
        if mesh is not None:
            if "model" not in mesh.axis_names:
                raise ValueError(
                    f"mesh axes {mesh.axis_names} carry no 'model' axis — "
                    f"serving tensor parallelism shards over 'model' "
                    f"(see distributed.serving_mesh)")
            msize = mesh.shape["model"]
            has_attn = any(kind.mixer in ("attn", "hymba")
                           for kind, _ in model.cfg.segments())
            if msize > 1 and has_attn \
                    and model.cfg.num_kv_heads % msize != 0:
                raise ValueError(
                    f"num_kv_heads={model.cfg.num_kv_heads} is not "
                    f"divisible by the {msize}-way 'model' mesh axis — "
                    f"page pools shard over KV heads; choose a mesh size "
                    f"that divides Hkv (or mesh=None)")
        self.max_seq = max_seq or model.cfg.max_seq_len
        self.sampler = sampler
        self.eos_id = eos_id
        self._prefill = self._exec_jit(model.prefill)
        donate = (1,) if donate_cache else ()
        self._step = self._exec_jit(self._decode_one, donate_argnums=donate)
        # streaming/continuous-batching state (built lazily on first submit)
        self.num_slots = num_slots
        self.page_size = page_size
        self._num_pages = num_pages
        self._seed = seed
        # page-pool storage regime: None follows cfg.kv_quant; "int8"
        # serves int8 pages under a float model (quantize-on-commit,
        # dequant fused into the paged decode read)
        if kv_quant not in (None, "none", "int8"):
            raise ValueError(f"unknown kv_quant {kv_quant!r}")
        self.kv_quant = model.cfg.kv_quant if kv_quant is None else kv_quant
        # chunked prefill: None = auto (chunked whenever the arch's paged
        # cache is pure kv_pool), True = require it, False = one-shot
        # per-request prefill (the PR-2 baseline path)
        if prefill_chunk < 1:
            raise ValueError("prefill_chunk must be ≥ 1")
        self.prefill_chunk = prefill_chunk
        self.chunked_prefill = chunked_prefill
        # speculative decoding: "ngram" (prompt-lookup self-drafter, no
        # second model) or "draft_model" (greedy small-model drafter — pass
        # draft_model + draft_params, or a custom draft_fn for testing)
        if spec_decode not in (None, "ngram", "draft_model"):
            raise ValueError(f"unknown spec_decode {spec_decode!r}")
        if spec_decode is not None and spec_k < 1:
            raise ValueError("spec_k must be ≥ 1")
        if spec_decode == "draft_model" and draft_model is None \
                and draft_fn is None:
            raise ValueError("spec_decode='draft_model' needs draft_model "
                             "(+ draft_params) or a draft_fn")
        if draft_model is not None:
            chunkable = self._cache_chunkable(jax.eval_shape(
                lambda: draft_model.init_paged_cache(1, 2, page_size,
                                                     page_size)))
            if not chunkable:
                raise ValueError(
                    "draft_model keeps bounded per-slot sequential state "
                    "(ring/SSM/MLA) — the draft cache must be pure dense "
                    "full attention")
        # tree speculation: drafts branch (a primary chain + alternate
        # first tokens), one chunk dispatch verifies every branch under
        # the kernel's ancestor mask, and the device-side walk + KV
        # compaction keep greedy streams token-identical to sequential
        # decode (see scheduler / _tree_greedy_fn)
        if spec_tree and spec_decode is None:
            raise ValueError("spec_tree needs a drafter — set "
                             "spec_decode='ngram' or 'draft_model'")
        if spec_tree and spec_tree_fanout < 1:
            raise ValueError("spec_tree_fanout must be ≥ 1")
        self.spec_decode = spec_decode
        self.spec_k = spec_k
        self.spec_adaptive = spec_adaptive
        self.spec_tree = spec_tree
        self.spec_tree_fanout = spec_tree_fanout
        self.spec_ngram_max = spec_ngram_max
        self.draft_model = draft_model
        self.draft_params = draft_params
        self._custom_draft_fn = draft_fn
        # SLO-aware preemption: priority classes on submit(), victim
        # spill to a host-memory page tier, zero-recompute restore.
        # admission="optimistic" drops the worst-case decode reservation
        # (preemption becomes the safety valve when the pool runs dry).
        if admission not in ("reserved", "optimistic"):
            raise ValueError(f"unknown admission policy {admission!r}")
        if admission == "optimistic" and not preemption:
            raise ValueError("admission='optimistic' requires "
                             "preemption=True — without spill as a safety "
                             "valve a drained pool would fail extend()")
        self.preemption = preemption
        self.admission = admission
        self._next_rid = 0
        self._scheduler: Scheduler | None = None
        self._paged_cache = None

    def _decode_one(self, params, cache, token, pos, key):
        logits, cache = self.model.decode_step(params, cache, token, pos)
        nxt = sample(logits, self.sampler, key)
        return nxt, cache, logits

    def generate(self, batch: dict, max_new_tokens: int,
                 key=None) -> np.ndarray:
        """Host-loop generation with EOS early-exit. Returns [B, max_new]."""
        key = key if key is not None else jax.random.PRNGKey(0)
        b = next(iter(batch.values())).shape[0]
        cache = self.model.init_cache(b, self.max_seq)
        cache, logits, pos = self._prefill(self.params, batch, cache)
        token = sample(logits, self.sampler, key)
        out = [np.asarray(token)]
        finished = np.zeros(b, bool)
        for t in range(max_new_tokens - 1):
            key, sub = jax.random.split(key)
            token, cache, logits = self._step(self.params, cache, token,
                                              pos, sub)
            pos = pos + 1
            tok_np = np.asarray(token)
            tok_np = np.where(finished, self.eos_id, tok_np)
            finished |= tok_np == self.eos_id
            out.append(tok_np)
            if self.eos_id >= 0 and finished.all():
                break
        return np.stack(out, axis=1)

    # ------------------------------------------------------------ streaming
    # submit()/step()/collect() — continuous batching over the paged cache.

    def _serving_init(self) -> Scheduler:
        if self.max_seq % self.page_size:
            raise ValueError("max_seq must be a multiple of page_size")
        pages_per_slot = self.max_seq // self.page_size
        num_pages = self._num_pages
        if num_pages is None:   # full capacity: every slot can hit max_seq
            num_pages = self.num_slots * pages_per_slot + 1
        pager = KVPager(PagerConfig(num_pages=num_pages,
                                    page_size=self.page_size,
                                    num_slots=self.num_slots,
                                    pages_per_slot=pages_per_slot,
                                    optimistic=(self.admission
                                                == "optimistic")))
        self._paged_cache = self.model.init_paged_cache(
            self.num_slots, num_pages, self.page_size, self.max_seq,
            kv_quant=self.kv_quant)
        chunkable = self._cache_chunkable(self._paged_cache)
        chunked = chunkable if self.chunked_prefill is None \
            else self.chunked_prefill
        if chunked and not chunkable:
            raise ValueError(
                "chunked_prefill=True but the arch keeps bounded per-slot "
                "sequential state (ring/SSM/MLA) — only pure "
                "paged-attention caches support the chunked path")
        if self.spec_decode is not None and not chunked:
            raise ValueError(
                "spec_decode requires the chunked serving path (verify "
                "runs are multi-token rows of the unified chunk dispatch)")
        if self._mesh is not None and not chunked:
            raise ValueError(
                "mesh-sharded serving requires the chunked (token-budget) "
                "path: archs with bounded per-slot sequential state "
                "(ring/SSM/MLA) and the one-shot baseline stay "
                "single-device — pass mesh=None")
        if self.preemption and not chunked:
            raise ValueError(
                "preemption requires the chunked serving path: restore "
                "re-enters the unified chunk dispatch at the commit "
                "watermark, which one-shot prefill does not track")
        self._key = jax.random.PRNGKey(self._seed)
        self._tables_version = -1
        self._tables_dev = None
        self._tables_sliced = {}
        self._init_mesh_placement()
        if self.preemption:
            self._init_spill_tier()
        if chunked:
            # ONE compiled step for everything: prefill chunks + decode
            # token runs packed into a fixed [num_slots, c] block
            self._chunk_sampled = self._jit_dispatch(self._chunk_step_fn,
                                                     n_host=8, n_out=2)
            self._chunk_greedy = self._jit_dispatch(self._chunk_greedy_fn,
                                                    n_host=5, n_out=2)
            draft_fn = None
            sched_spec = None
            if self.spec_decode is not None:
                self._spec_greedy = self._jit_dispatch(self._spec_greedy_fn,
                                                       n_host=6, n_out=3)
                self._spec_sampled = self._jit_dispatch(
                    self._spec_sampled_fn, n_host=9, n_out=3)
                if self.spec_tree:
                    self._tree_greedy = self._jit_dispatch(
                        self._tree_greedy_fn, n_host=9, n_out=4)
                    self._tree_sampled = self._jit_dispatch(
                        self._tree_sampled_fn, n_host=12, n_out=4)
                sched_spec = "ngram" if self.spec_decode == "ngram" \
                    else "draft_fn"
                if self.spec_decode == "draft_model":
                    draft_fn = self._custom_draft_fn
                    if draft_fn is None:
                        self._draft_init()
                        draft_fn = self._draft_tree_fn if self.spec_tree \
                            else self._draft_fn
            return Scheduler(pager, run_batch=self._exec_run_batch,
                             chunk_size=self.prefill_chunk,
                             spec_decode=sched_spec, spec_k=self.spec_k,
                             adaptive_spec_k=self.spec_adaptive,
                             spec_tree=self.spec_tree,
                             spec_tree_fanout=self.spec_tree_fanout,
                             draft_fn=draft_fn,
                             ngram_max=self.spec_ngram_max,
                             preemption=self.preemption,
                             spill_fn=(self._exec_spill
                                       if self.preemption else None),
                             restore_fn=(self._exec_restore
                                         if self.preemption else None))
        # one-shot path: one dispatch per admission fusing prefill + page
        # commit + first sample (start_page static: commit skips the
        # aliased shared-prefix pages), jit per prompt length
        self._prefill_fused = self._exec_jit(self._prefill_commit_fn,
                                             donate_argnums=(1,),
                                             static_argnums=(8,))
        self._decode_paged = self._exec_jit(self._decode_paged_fn,
                                            donate_argnums=(1,))
        self._decode_greedy = self._exec_jit(self._decode_greedy_fn,
                                             donate_argnums=(1,))
        return Scheduler(pager, prefill_commit=self._exec_prefill_commit,
                         decode=self._exec_decode)

    @staticmethod
    def _cache_chunkable(cache) -> bool:
        """True when every cache entry is a page pool (no per-slot
        sequential state), i.e. the arch can run the chunked path."""
        return all(set(entry) == {"kv_pool"} for entry in cache.values())

    # --- tensor-parallel placement ----------------------------------------
    def _init_mesh_placement(self):
        """Shard params + page pools over the serving mesh (no-op without
        one). Weights follow `param_pspec` (column/row-parallel linears,
        vocab-parallel head), pools follow `paged_cache_pspec` (KV heads
        over ``model``); `self.params` itself stays untouched so the
        static-batch `generate` baselines keep their single-device path.
        """
        if self._mesh is None:
            self._params_run = self.params
            return
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.distributed import sharding as shd
        mesh = self._mesh
        self._repl_sh = NamedSharding(mesh, P())
        params = shd.tag_tensor_parallel(self.params, mesh, self.cfg)
        self._param_sh = shd.make_sharding(params, mesh, shd.param_pspec,
                                           self.cfg)
        self._cache_sh = shd.make_sharding(self._paged_cache, mesh,
                                           shd.paged_cache_pspec)
        self._params_run = jax.device_put(params, self._param_sh)
        self._paged_cache = jax.device_put(self._paged_cache, self._cache_sh)

    # --- host-memory page tier (preemption spill/restore) -----------------
    def _init_spill_tier(self):
        """Compile the page-strip movers behind `KVPager.spill`/`restore`.

        Gather reads ``pool[:, ids]`` strips out of every kv_pool leaf —
        int8 codes + scale strips when the pool is quantized, so the host
        tier holds the pages **int8-recompressed**, never re-inflated.
        Scatter writes them into freshly drawn pages with the cache
        donated (the pool buffers mutate in place like every other
        dispatch). Under a mesh the strips cross the tier replicated
        (`distributed.sharding.spill_sharding`): the gather all-gathers
        each device's head shard in-dispatch, the scatter re-stripes on
        the way back in, and the host-side page ids stay device-agnostic.
        """
        if self._mesh is None:
            self._spill_sh = None
            self._spill_gather = self._exec_jit(self._spill_gather_fn)
            self._spill_scatter = self._exec_jit(self._spill_scatter_fn,
                                                 donate_argnums=(0,))
            return
        from repro.distributed import sharding as shd
        self._spill_sh = shd.spill_sharding(self._mesh)
        self._spill_gather = self._exec_jit(
            self._spill_gather_fn,
            in_shardings=(self._cache_sh, self._spill_sh),
            out_shardings=self._spill_sh)
        self._spill_scatter = self._exec_jit(
            self._spill_scatter_fn, donate_argnums=(0,),
            in_shardings=(self._cache_sh, self._spill_sh, self._spill_sh),
            out_shardings=self._cache_sh)

    def _spill_gather_fn(self, cache, ids):
        """cache, page ids [n] → {seg: {leaf: [L, n, P, ...] strips}}."""
        return {seg: {k: leaf[:, ids]
                      for k, leaf in entry["kv_pool"].items()}
                for seg, entry in cache.items()}

    def _spill_scatter_fn(self, cache, ids, strips):
        """Write gathered strips into pages ``ids`` of every pool leaf."""
        return {seg: {"kv_pool": {
                    k: leaf.at[:, ids].set(
                        strips[seg][k].astype(leaf.dtype))
                    for k, leaf in entry["kv_pool"].items()}}
                for seg, entry in cache.items()}

    @staticmethod
    def _spill_bucket(n: int) -> int:
        """Geometric page-count bucket for spill strips, so the compiled
        gather/scatter family stays O(log pages_per_slot); the pad ids
        point at the scratch page 0, whose content is never read."""
        b = 1
        while b < n:
            b *= 2
        return b

    def _spill_ids_dev(self, ids: list[int], n: int):
        padded = np.zeros(self._spill_bucket(n), np.int32)
        padded[:n] = ids
        if self._mesh is not None:
            return jax.device_put(padded, self._spill_sh)
        return jnp.asarray(padded)

    def _exec_spill(self, phys_ids: list[int]) -> dict:
        """Scheduler spill hook: gather ``phys_ids``'s pool bytes BEFORE
        the pager releases those pages. The gather is dispatched async —
        the strips snapshot the pre-release cache value (functional
        arrays), and the device→host DMA overlaps the decode dispatches
        that follow; nothing blocks until the strips are needed again."""
        n = len(phys_ids)
        strips = self._spill_gather(self._paged_cache,
                                    self._spill_ids_dev(phys_ids, n))
        for leaf in jax.tree.leaves(strips):
            if hasattr(leaf, "copy_to_host_async"):
                leaf.copy_to_host_async()
        return {"n": n, "strips": strips}

    def _exec_restore(self, handle: dict, fresh_ids: list[int]) -> None:
        """Scheduler restore hook: scatter the parked strips into the
        freshly drawn pages (the pager already rebuilt the page table)."""
        assert len(fresh_ids) == handle["n"]
        self._paged_cache = self._spill_scatter(
            self._paged_cache, self._spill_ids_dev(fresh_ids, handle["n"]),
            handle["strips"])

    # --- cross-engine KV page handoff (disaggregated prefill/decode) ------
    def _ensure_spill_movers(self) -> None:
        """The disagg handoff path reuses the preemption spill movers
        (gather/scatter over **replicated** strips — which is exactly why
        a decode engine on a different mesh can adopt them); build them
        lazily for engines that never enabled preemption. The wrappers
        are `_exec_jit` caches: nothing compiles until first use."""
        if self._scheduler is None:
            self._scheduler = self._serving_init()
        if not hasattr(self, "_spill_gather"):
            self._init_spill_tier()

    def handoff_gather(self, phys_ids: list[int]) -> dict:
        """Gather ``phys_ids``'s pool bytes for a cross-engine handoff.

        Dispatched async like `_exec_spill` — the strips snapshot the
        current cache value (functional arrays: the pager may free the
        slot immediately after), and the device→host DMA overlaps
        whatever the caller dispatches next (the decode engine's step, in
        `DisaggController`). `handoff_wire` materializes the wire image.
        """
        self._ensure_spill_movers()
        return self._exec_spill(phys_ids)

    def handoff_wire(self, handle: dict) -> tuple[dict, int]:
        """Block on a `handoff_gather` and return ``(strips, wire_bytes)``.

        Strips come back as host numpy trimmed to the real page count —
        the honest wire image: int8 pools ship codes + per-position scale
        strips (~2× fewer bytes than bf16), and because the gather leaves
        the mesh replicated (`distributed.sharding.spill_sharding`) the
        image is mesh-agnostic — a decode engine on a *different* mesh
        adopts it unchanged.
        """
        n = handle["n"]
        strips = jax.tree.map(lambda a: np.asarray(a)[:, :n],
                              handle["strips"])
        wire = sum(leaf.nbytes for leaf in jax.tree.leaves(strips))
        return strips, wire

    def handoff_scatter(self, strips: dict, strip_idx: list[int],
                        fresh_ids: list[int]) -> None:
        """Scatter wire strips ``strip_idx`` into this engine's freshly
        drawn pages (the pager's `adopt` already rebuilt the page table;
        pages it aliased against the local prefix index ship nothing and
        are absent here)."""
        self._ensure_spill_movers()
        if not fresh_ids:
            return
        assert len(strip_idx) == len(fresh_ids)
        n = len(fresh_ids)
        idx = np.zeros(self._spill_bucket(n), np.int64)
        idx[:n] = strip_idx                 # pad cols land on scratch page 0
        sub = jax.tree.map(lambda a: a[:, idx], strips)
        self._paged_cache = self._spill_scatter(
            self._paged_cache, self._spill_ids_dev(fresh_ids, n), sub)

    @staticmethod
    def _exec_jit(fn, **jit_kw):
        """jit ``fn`` keyed on the ACTIVE `core.qlinear.ExecutionConfig`.

        `qlinear_apply` reads the execution config at trace time, so a
        plain ``jax.jit`` would bake in whatever was set at the first call
        and silently ignore every later `set_execution_config(...)`.
        Every call instead looks up (or traces) a compiled instance for
        the config active *now* — flipping impl/compute_dtype retraces on
        the very next step, with the config pinned for the whole trace via
        the `execution_config` context manager.
        """
        from repro.core.qlinear import execution_config, get_execution_config
        cache: dict = {}

        def active():
            cfg = get_execution_config()
            jitted = cache.get(cfg)
            if jitted is None:
                def traced(*a, _cfg=cfg):
                    with execution_config(_cfg):
                        return fn(*a)

                jitted = jax.jit(traced, **jit_kw)
                cache[cfg] = jitted
            return jitted

        def call(*args):
            return active()(*args)

        call.lower = lambda *args: active().lower(*args)

        # jax.jit's compiled-trace introspection, summed over the config
        # instances (tests bound the compile family through this)
        call._cache_size = lambda: sum(j._cache_size()
                                       for j in cache.values())
        return call

    def _jit_dispatch(self, fn, *, n_host: int, n_out: int):
        """jit one serving dispatch (cache donated), keyed on the active
        execution config (`_exec_jit`).

        Under a mesh the function is traced with the mesh active (so the
        model's `constrain` calls resolve) and pinned with EXPLICIT in/out
        shardings: params and cache as sharded, the ``n_host`` trailing
        operands (page tables, token blocks, per-row metadata, PRNG keys)
        and every output but the cache replicated, and the cache's out
        sharding equal to its in sharding — the donated pool buffers
        round-trip without resharding, step after step. The param
        shardings cover `PackedLinear` leaves too (`param_pspec` addresses
        them by leaf name), so the quantized model serves sharded through
        the exact same dispatches.
        """
        if self._mesh is None:
            return self._exec_jit(fn, donate_argnums=(1,))
        from repro.distributed.sharding import use_mesh

        def traced(*args):
            with use_mesh(self._mesh):
                return fn(*args)

        in_sh = (self._param_sh, self._cache_sh) + (self._repl_sh,) * n_host
        out_sh = (self._repl_sh,) * (n_out - 1) + (self._cache_sh,)
        return self._exec_jit(traced, donate_argnums=(1,),
                              in_shardings=in_sh, out_shardings=out_sh)

    def _prefill_commit_fn(self, params, cache, tokens, slot, pages,
                           temp, topk, key, start_page=0):
        """tokens [1, S] → (first sampled token, updated paged cache).

        ``start_page`` (static) skips committing the leading shared-prefix
        pages — their content is already resident and aliased read-only.
        """
        pre = self.model.init_cache(1, tokens.shape[1])
        pre, logits, _ = self.model.prefill(params, {"tokens": tokens}, pre)
        cache = commit_prefill(cache, pre, slot, pages,
                               page_size=self.page_size,
                               start_page=start_page)
        tok = sample_batched(logits, temp[None], topk[None], key)
        return tok[0], cache

    def _chunk_step_fn(self, params, cache, page_tables, tokens, pos,
                       row_slots, sample_idx, temps, topks, key):
        """Unified token-budget step: tokens/pos [B, C] → sampled [B].

        ``page_tables`` is the (bucketed) [num_slots, n_blocks] table;
        row b of the dispatch reads/writes slot ``row_slots[b]``'s row.
        """
        logits, cache = self.model.chunk_step(params, cache, tokens, pos,
                                              sample_idx,
                                              page_table=page_tables[
                                                  row_slots])
        return sample_batched(logits, temps, topks, key), cache

    def _chunk_greedy_fn(self, params, cache, page_tables, tokens, pos,
                         row_slots, sample_idx):
        """Greedy fast path: no PRNG, no sort/top-k machinery."""
        logits, cache = self.model.chunk_step(params, cache, tokens, pos,
                                              sample_idx,
                                              page_table=page_tables[
                                                  row_slots])
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    # --- speculative verify steps -----------------------------------------
    # A verify row is just a multi-token decode row of the unified chunk
    # dispatch: tokens[b, sample_idx[b] : sample_idx[b] + 1 + n_draft[b]]
    # is the run [last_sampled, d_1 … d_k] at consecutive positions, and
    # `chunk_step(num_logits = spec_k + 1)` returns the target
    # distribution after each of them. Acceptance runs on device, so the
    # vocab-sized distributions never leave it: each row returns its
    # leading-accept count and ONE corrected/bonus token.

    def _spec_gather_drafts(self, tokens, sample_idx, r):
        """draft_next [B, R]: the input token each gathered logit must
        predict — tokens at in-row index sample_idx + j + 1 (clipped;
        indices past a row's run are masked by n_draft downstream)."""
        c = tokens.shape[1]
        j = jnp.arange(r, dtype=jnp.int32)[None, :]
        nxt = jnp.clip(sample_idx[:, None].astype(jnp.int32) + j + 1,
                       0, c - 1)
        return jnp.take_along_axis(tokens, nxt, axis=1), j

    def _spec_greedy_fn(self, params, cache, page_tables, tokens, pos,
                        row_slots, sample_idx, n_draft):
        """Greedy verify: accept the longest draft prefix that matches the
        argmax chain; the fix token is the argmax after it (the corrected
        token on rejection, the bonus token on full acceptance) — exactly
        the tokens sequential greedy decode would emit."""
        r = self.spec_k + 1
        logits, cache = self.model.chunk_step(
            params, cache, tokens, pos, sample_idx,
            page_table=page_tables[row_slots], num_logits=r)
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)        # [B, R]
        draft_next, j = self._spec_gather_drafts(tokens, sample_idx, r)
        ok = (draft_next == g) & (j < n_draft[:, None])
        n_acc = jnp.cumprod(ok.astype(jnp.int32), axis=1).sum(axis=1)
        fix = jnp.take_along_axis(g, n_acc[:, None], axis=1)[:, 0]
        return fix, n_acc, cache

    def _spec_sampled_fn(self, params, cache, page_tables, tokens, pos,
                         row_slots, sample_idx, n_draft, temps, topks, key):
        """Acceptance sampling for point-mass drafts, distribution-faithful
        per row: draft d_j is accepted with probability p(d_j) under the
        row's (temperature / top-k filtered) target distribution; on the
        first rejection the fix token is drawn from the residual — the
        target with d_j removed, renormalized — and on full acceptance
        from the plain target at the bonus position. Marginally the
        emitted stream is distributed exactly as sequential sampling
        (greedy rows reduce to the argmax chain of `_spec_greedy_fn`).
        Rows with ``n_draft == 0`` degenerate to one plain sample at
        ``sample_idx`` — the pre-speculation contract.
        """
        r = self.spec_k + 1
        logits, cache = self.model.chunk_step(
            params, cache, tokens, pos, sample_idx,
            page_table=page_tables[row_slots], num_logits=r)   # [B, R, V]
        v = logits.shape[-1]
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits / jnp.where(temps > 0, temps, 1.0)[:, None, None]
        kidx = jnp.broadcast_to(
            jnp.clip(topks - 1, 0, v - 1)[:, None, None],
            (logits.shape[0], r, 1))
        desc = -jnp.sort(-scaled, axis=-1)
        kth = jnp.take_along_axis(desc, kidx, axis=-1)
        filtered = jnp.where(scaled < kth, -1e30, scaled)
        scaled = jnp.where((topks > 0)[:, None, None], filtered, scaled)
        probs = jax.nn.softmax(scaled, axis=-1)
        draft_next, j = self._spec_gather_drafts(tokens, sample_idx, r)
        p_draft = jnp.take_along_axis(probs, draft_next[..., None],
                                      axis=-1)[..., 0]
        ku, kr, kb = jax.random.split(key, 3)
        u = jax.random.uniform(ku, p_draft.shape)
        greedy = (temps == 0.0)[:, None]
        ok = jnp.where(greedy, draft_next == g, u < p_draft)
        ok &= j < n_draft[:, None]
        n_acc = jnp.cumprod(ok.astype(jnp.int32), axis=1).sum(axis=1)
        dmask = jax.nn.one_hot(draft_next, v, dtype=bool)
        rej = jax.random.categorical(kr, jnp.where(dmask, -1e30, scaled))
        bon = jax.random.categorical(kb, scaled)
        # greedy rows: the residual argmax IS the global argmax (a greedy
        # rejection means draft ≠ argmax), and the bonus is the argmax too
        rej = jnp.where(greedy, g, rej.astype(jnp.int32))
        bon = jnp.where(greedy, g, bon.astype(jnp.int32))
        fix_rej = jnp.take_along_axis(rej, n_acc[:, None], axis=1)[:, 0]
        fix_bon = jnp.take_along_axis(bon, n_acc[:, None], axis=1)[:, 0]
        fix = jnp.where(n_acc == n_draft, fix_bon, fix_rej)
        return fix, n_acc, cache

    # --- tree-speculative verify steps ------------------------------------
    # A tree verify row carries a whole token TREE at contiguous KV slots
    # (node i at slot q + 1 + i, in node-index order): `chunk_step` runs
    # ONE weight pass with the per-row ancestor mask routing each node's
    # attention to exactly its own root-path, and with ``rpos`` giving
    # nodes their LOGICAL position q + depth(i) (siblings share a depth,
    # so their RoPE angles match what sequential decode would use). The
    # device-side walk picks the deepest accepted branch, and the KV of
    # that branch is compacted into the contiguous slots sequential
    # decode would have written — after the host truncates the losing
    # branches, the paged cache is bit-identical to a sequential run,
    # which is what makes greedy tree speculation token-identical
    # end-to-end (across int8 pools, prefix sharing, and the mesh).

    def _tree_compact(self, cache, pt, q, path, n_acc):
        """Gather-then-scatter the accepted branch's strips into place.

        For accepted depth ``t`` (1-based), the node at in-row index
        ``path[:, t-1]`` moves from KV slot ``q + path[:, t-1]`` to slot
        ``q + t`` in every pool leaf (int8 codes and scale strips
        included). All gathers complete before any scatter (functional
        updates), so chained moves within a row cannot clobber each
        other; no-op moves (node already in place), depths beyond
        ``n_acc`` and padding rows (``q < 0``) are redirected to the
        scratch page 0, whose content is never read.
        """
        ps = self.page_size
        dmax = path.shape[1]
        t = jnp.arange(1, dmax + 1, dtype=jnp.int32)[None, :]
        src = q[:, None] + path
        dst = q[:, None] + t
        live = (t <= n_acc[:, None]) & (path != t) & (q[:, None] >= 0)
        src_i = jnp.where(live, src, 0)
        dst_i = jnp.where(live, dst, 0)
        src_pg = jnp.take_along_axis(pt, src_i // ps, axis=1)
        dst_pg = jnp.take_along_axis(pt, dst_i // ps, axis=1)
        sp = jnp.where(live, src_pg, 0).reshape(-1)
        so = jnp.where(live, src_i % ps, 0).reshape(-1)
        dp = jnp.where(live, dst_pg, 0).reshape(-1)
        do = jnp.where(live, dst_i % ps, 0).reshape(-1)
        return {seg: {"kv_pool": {
                    k: leaf.at[:, dp, :, do].set(leaf[:, sp, :, so])
                    for k, leaf in entry["kv_pool"].items()}}
                for seg, entry in cache.items()}

    def _tree_greedy_fn(self, params, cache, page_tables, tokens, pos,
                        row_slots, sample_idx, n_draft, rpos, amask,
                        parents):
        """Greedy tree verify: one weight pass over every branch, then the
        argmax walk — emits exactly the tokens sequential greedy decode
        would (rows with ``n_draft == 0`` degenerate to plain decode)."""
        r = self.spec_k + 1
        pt = page_tables[row_slots]
        logits, cache = self.model.chunk_step(
            params, cache, tokens, pos, sample_idx, page_table=pt,
            num_logits=r, rpos=rpos, amask=amask)
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        fix, n_acc, path = _tree_walk_greedy(g, tokens, parents, n_draft,
                                             self.spec_k)
        cache = self._tree_compact(cache, pt, pos[:, 0], path, n_acc)
        return fix, n_acc, path, cache

    def _tree_sampled_fn(self, params, cache, page_tables, tokens, pos,
                         row_slots, sample_idx, n_draft, rpos, amask,
                         parents, temps, topks, key):
        """Sampled tree verify: residual acceptance over sibling branches
        (see `_tree_walk_sampled`); greedy rows ride a one-hot target, so
        mixed-sampler steps keep their greedy rows argmax-exact."""
        r = self.spec_k + 1
        pt = page_tables[row_slots]
        logits, cache = self.model.chunk_step(
            params, cache, tokens, pos, sample_idx, page_table=pt,
            num_logits=r, rpos=rpos, amask=amask)
        v = logits.shape[-1]
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits / jnp.where(temps > 0, temps, 1.0)[:, None, None]
        kidx = jnp.broadcast_to(
            jnp.clip(topks - 1, 0, v - 1)[:, None, None],
            (logits.shape[0], r, 1))
        desc = -jnp.sort(-scaled, axis=-1)
        kth = jnp.take_along_axis(desc, kidx, axis=-1)
        filtered = jnp.where(scaled < kth, -1e30, scaled)
        scaled = jnp.where((topks > 0)[:, None, None], filtered, scaled)
        probs = jax.nn.softmax(scaled, axis=-1)
        probs = jnp.where((temps == 0.0)[:, None, None],
                          jax.nn.one_hot(g, v, dtype=probs.dtype), probs)
        fix, n_acc, path = _tree_walk_sampled(probs, tokens, parents,
                                              n_draft, self.spec_k, key)
        cache = self._tree_compact(cache, pt, pos[:, 0], path, n_acc)
        return fix, n_acc, path, cache

    # --- draft-model drafting (spec_decode="draft_model") -----------------
    # The draft model keeps a DENSE per-slot cache [num_slots, max_seq]
    # (it is small by construction — paging it would buy nothing): lazy
    # per-slot prefill when a request starts decoding, then k + 1 greedy
    # decode steps per scheduler step (the extra step writes the last
    # draft's KV, so after full acceptance the draft cache is already
    # caught up to the bonus token's position). Rejected-draft KV is
    # simply overwritten — positions are absolute, and the next step's
    # inputs rewrite every position past the accepted stream before any
    # causal read can see it.

    def _draft_init(self):
        self._draft_cache = self.draft_model.init_cache(self.num_slots,
                                                        self.max_seq)
        self._draft_rid: dict[int, int] = {}
        self._draft_prefill = self._exec_jit(self._draft_prefill_fn,
                                             donate_argnums=(1,))
        self._draft_step = self._exec_jit(self._draft_step_fn,
                                          donate_argnums=(1,))
        self._draft_top = self._exec_jit(self._draft_top_fn,
                                         donate_argnums=(1,),
                                         static_argnums=(4,))

    def _draft_prefill_fn(self, params, dcache, tokens, slot):
        """tokens [1, S] → draft cache with slot's rows 0..S-1 rewritten.

        ``tokens`` is the context zero-padded up to a geometric length
        bucket (`_draft_bucket`), so this compiles O(log max_seq) times
        instead of once per context length. The pad tail's KV (a zero
        continuation of the real prefix) lands at positions ≥ the real
        context length — exactly the positions drafting rewrites before
        any causal read can see them, the same dead-KV argument that
        covers rejected drafts.
        """
        from repro.serving.kv_pager import _commit_dense_leaf
        pre = self.draft_model.init_cache(1, tokens.shape[1])
        pre, _, _ = self.draft_model.prefill(params, {"tokens": tokens}, pre)
        return {seg: {"kv": {k: _commit_dense_leaf(entry["kv"][k],
                                                   pre[seg]["kv"][k], slot)
                             for k in entry["kv"]}}
                for seg, entry in dcache.items()}

    def _draft_bucket(self, n: int) -> int:
        """Geometric draft-prefill length bucket covering ``n`` tokens."""
        b = 8
        while b < n:
            b *= 2
        return min(b, self.max_seq)

    def _draft_step_fn(self, params, dcache, token, pos):
        logits, dcache = self.draft_model.decode_step(params, dcache,
                                                      token, pos)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), dcache

    def _draft_top_fn(self, params, dcache, token, pos, f):
        """Top-``f`` next tokens per row (column 0 = the argmax) — the
        branching first step of tree drafting."""
        logits, dcache = self.draft_model.decode_step(params, dcache,
                                                      token, pos)
        return jax.lax.top_k(logits, f)[1].astype(jnp.int32), dcache

    def _draft_fn(self, reqs):
        """Scheduler drafting hook: [(slot, rid, ctx, next_pos, k_eff)] →
        {slot: draft tokens} via greedy draft-model decode."""
        b = self.num_slots
        for slot, rid, ctx, q, _k in reqs:
            if self._draft_rid.get(slot) != rid:   # slot reused: re-prefill
                padded = np.zeros(self._draft_bucket(q), np.int32)
                padded[:q] = ctx[:q]
                self._draft_cache = self._draft_prefill(
                    self.draft_params, self._draft_cache,
                    jnp.asarray(padded)[None, :], jnp.int32(slot))
                self._draft_rid[slot] = rid
        tok = np.zeros(b, np.int32)
        posv = np.zeros(b, np.int32)
        active: dict[int, int] = {}
        for slot, _rid, ctx, q, k in reqs:
            tok[slot] = int(ctx[-1])
            posv[slot] = q
            active[slot] = k
        props: dict[int, list[int]] = {slot: [] for slot in active}
        k_max = max(active.values())
        for i in range(k_max + 1):
            nxt, self._draft_cache = self._draft_step(
                self.draft_params, self._draft_cache,
                jnp.asarray(tok), jnp.asarray(posv))
            nxt = np.asarray(nxt)
            for slot, k in active.items():
                if i < k:
                    props[slot].append(int(nxt[slot]))
                    tok[slot] = int(nxt[slot])
                    posv[slot] += 1
                # i ≥ k: frozen — the row idempotently rewrites its last
                # draft's KV (rows of inactive slots idle at position 0,
                # which the next per-slot prefill rewrites)
        return props

    def _draft_tree_fn(self, reqs):
        """Tree drafting hook (``spec_tree``): the draft model's top-
        ``fanout`` first-step tokens branch the root — the top-1 opens
        the primary chain (continued greedily), the rest become depth-1
        alternates hedging a chain miss. Alternates consume node budget:
        the chain keeps ``k_eff − #alternates`` nodes, so the row width
        never exceeds the linear verify bucket. Same lazy per-slot
        dense-cache prefill and idempotent-rewrite argument as
        `_draft_fn`; requests carry a trailing ``fanout`` element."""
        b = self.num_slots
        for slot, rid, ctx, q, _k, _f in reqs:
            if self._draft_rid.get(slot) != rid:   # slot reused: re-prefill
                padded = np.zeros(self._draft_bucket(q), np.int32)
                padded[:q] = ctx[:q]
                self._draft_cache = self._draft_prefill(
                    self.draft_params, self._draft_cache,
                    jnp.asarray(padded)[None, :], jnp.int32(slot))
                self._draft_rid[slot] = rid
        tok = np.zeros(b, np.int32)
        posv = np.zeros(b, np.int32)
        chain: dict[int, int] = {}        # slot → chain length left
        fans: dict[int, int] = {}
        for slot, _rid, ctx, q, k, f in reqs:
            tok[slot] = int(ctx[-1])
            posv[slot] = q
            chain[slot] = k
            fans[slot] = f
        fmax = max(max(fans.values()), 1)
        nodes: dict[int, list[tuple[int, int]]] = {s: [] for s in chain}
        last: dict[int, int] = {}         # slot → chain tip node index
        alts: dict[int, list[int]] = {}
        for i in range(max(chain.values()) + 1):
            if i == 0:
                top, self._draft_cache = self._draft_top(
                    self.draft_params, self._draft_cache,
                    jnp.asarray(tok), jnp.asarray(posv), fmax)
                top = np.asarray(top)
                nxt = top[:, 0]
            else:
                nxt, self._draft_cache = self._draft_step(
                    self.draft_params, self._draft_cache,
                    jnp.asarray(tok), jnp.asarray(posv))
                nxt = np.asarray(nxt)
            for slot, k in chain.items():
                if i == 0:
                    a = [int(t) for t in top[slot, 1:fans[slot]]][:k - 1]
                    alts[slot] = a
                    chain[slot] = k - len(a)   # chain keeps the rest
                    nodes[slot].append((int(nxt[slot]), -1))
                    last[slot] = 0
                    tok[slot] = int(nxt[slot])
                    posv[slot] += 1
                elif i < chain[slot]:
                    nodes[slot].append((int(nxt[slot]), last[slot]))
                    last[slot] = len(nodes[slot]) - 1
                    tok[slot] = int(nxt[slot])
                    posv[slot] += 1
                # i ≥ chain length: frozen, same dead-KV argument as above
        for slot, a in alts.items():
            nodes[slot].extend((t, -1) for t in a)
        return nodes

    def _decode_paged_fn(self, params, cache, page_tables, token, pos,
                         temps, topks, key):
        logits, cache = self.model.decode_step(params, cache, token, pos,
                                               page_table=page_tables)
        return sample_batched(logits, temps, topks, key), cache

    def _decode_greedy_fn(self, params, cache, page_tables, token, pos):
        """Greedy fast path: no PRNG, no sort/top-k machinery."""
        logits, cache = self.model.decode_step(params, cache, token, pos,
                                               page_table=page_tables)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    # --- executor callables handed to the Scheduler (host-side glue) ------
    def _device_tables(self, n_blocks: int | None = None, host_tables=None):
        """Version-cached device copy of the pager's page tables, optionally
        sliced to the first ``n_blocks`` columns (the context bucket).
        ``host_tables`` lets an executor supply the host array it was
        handed (the Scheduler contract) instead of reading the pager."""
        pager = self._scheduler.pager
        if self._tables_version != pager.version:   # upload only on mutation
            src = pager.page_tables if host_tables is None else host_tables
            if self._mesh is not None:   # page IDs are device-agnostic:
                self._tables_dev = jax.device_put(src, self._repl_sh)
            else:                        # tables replicate across the mesh
                self._tables_dev = jnp.asarray(src)
            self._tables_version = pager.version
            self._tables_sliced = {}
        if n_blocks is None or n_blocks == self._tables_dev.shape[1]:
            return self._tables_dev
        if n_blocks not in self._tables_sliced:
            self._tables_sliced[n_blocks] = self._tables_dev[:, :n_blocks]
        return self._tables_sliced[n_blocks]

    def _context_bucket(self, max_pos: int) -> int:
        """Pages the unified step must read to cover ``max_pos``, rounded
        up to a geometric bucket (8, 16, 32, … pages, capped at slot
        capacity).

        The chunk dispatch's attention cost scales with the page-table
        width it reads, so reading the full slot capacity every step
        would make a long-context engine pay max_seq work from the first
        chunk. Bucketing keeps the compiled-variant family at
        O(log pages_per_slot) — independent of the prompt-length mix —
        while step cost tracks the actual committed context.
        """
        pps = self.max_seq // self.page_size
        need = max_pos // self.page_size + 1
        b = 8
        while b < need:
            b *= 2
        return min(b, pps)

    def _exec_run_batch(self, tokens, pos, row_slots, sample_idx, temps,
                        topks, n_draft=None, tree=None):
        st = self._scheduler.stats
        greedy = not temps.any() and not topks.any()
        spec = tree is not None or (n_draft is not None and n_draft.any())
        with span(st, "serve.upload"):
            tables = self._device_tables(self._context_bucket(int(pos.max())))
            # operands in the order every step variant takes them
            host = [tokens, pos, row_slots, sample_idx]
            if spec:
                host.append(n_draft)
            if tree is not None:
                host += [tree["rpos"], tree["amask"], tree["parents"]]
            if not greedy:
                host += [temps, topks]
            args = [jnp.asarray(a) for a in host]
            if not greedy:
                self._key, sub = jax.random.split(self._key)
                args.append(sub)
        if tree is not None:
            # tree verify: per-row ancestor masks + logical positions in,
            # (corrected token, accepted depth, accepted branch) out —
            # the accepted KV is already compacted on device
            fn = self._tree_greedy if greedy else self._tree_sampled
        elif spec:
            # at least one verify run: the speculative step returns, per
            # row, the leading-accept count + corrected/bonus token
            fn = self._spec_greedy if greedy else self._spec_sampled
        else:
            fn = self._chunk_greedy if greedy else self._chunk_sampled
        with span(st, "serve.dispatch"):
            *out, self._paged_cache = fn(self._params_run, self._paged_cache,
                                         tables, *args)
        with span(st, "serve.wait"):
            out = [np.asarray(o) for o in out]
        if len(out) > 1:
            return tuple(out)
        if n_draft is None:
            return out[0]
        return out[0], np.zeros(out[0].shape[0], np.int32)

    def warmup(self, sampled: bool = False) -> int:
        """Precompile the chunked step family: every geometric context
        bucket × every width bucket the run-length packer may pick
        (× the speculative verify variants when spec_decode is on, × the
        sampled variants on request). All-padding dispatches only touch
        the scratch page, so serving state is unaffected. Returns the
        number of variants compiled; no-op on the one-shot path (its
        prefill compiles per prompt length at admission)."""
        if self._scheduler is None:
            self._scheduler = self._serving_init()
        if not self._scheduler.chunked:
            return 0
        # enumerate the bucket families through _context_bucket and the
        # scheduler's width_family itself, so warmup can never drift from
        # the schedule the serving loop uses
        buckets = {self._context_bucket(p)
                   for p in range(0, self.max_seq, self.page_size)}
        b = self.num_slots
        n = 0
        for nb in sorted(buckets):
            tables = self._device_tables(nb)
            for c in self._scheduler.width_buckets:
                args = (jnp.zeros((b, c), jnp.int32),
                        jnp.full((b, c), -1, jnp.int32),
                        jnp.zeros(b, jnp.int32), jnp.zeros(b, jnp.int32))
                _, self._paged_cache = self._chunk_greedy(
                    self._params_run, self._paged_cache, tables, *args)
                n += 1
                if sampled:
                    self._key, sub = jax.random.split(self._key)
                    _, self._paged_cache = self._chunk_sampled(
                        self._params_run, self._paged_cache, tables, *args,
                        jnp.zeros(b, jnp.float32), jnp.zeros(b, jnp.int32),
                        sub)
                    n += 1
                if self.spec_decode is None or c < 2:
                    continue        # a width-1 row can never carry a draft
                nd = jnp.zeros(b, jnp.int32)
                _, _, self._paged_cache = self._spec_greedy(
                    self._params_run, self._paged_cache, tables, *args, nd)
                n += 1
                if sampled:
                    self._key, sub = jax.random.split(self._key)
                    _, _, self._paged_cache = self._spec_sampled(
                        self._params_run, self._paged_cache, tables, *args, nd,
                        jnp.zeros(b, jnp.float32), jnp.zeros(b, jnp.int32),
                        sub)
                    n += 1
                if not self.spec_tree:
                    continue
                # tree-verify variants: all-padding rows with an all-false
                # ancestor mask (nothing visible in-span → exact-zero rows)
                targs = args + (nd, jnp.full((b, c), -1, jnp.int32),
                                jnp.zeros((b, c, c), jnp.bool_),
                                jnp.full((b, c), -1, jnp.int32))
                _, _, _, self._paged_cache = self._tree_greedy(
                    self._params_run, self._paged_cache, tables, *targs)
                n += 1
                if sampled:
                    self._key, sub = jax.random.split(self._key)
                    _, _, _, self._paged_cache = self._tree_sampled(
                        self._params_run, self._paged_cache, tables, *targs,
                        jnp.zeros(b, jnp.float32), jnp.zeros(b, jnp.int32),
                        sub)
                    n += 1
        return n

    def lowered_step(self, width: int | None = None):
        """The greedy chunk step, lowered (`jax.stages.Lowered`) for a
        ``[num_slots, width]`` token block (default: the widest bucket)
        over the largest context bucket, with the all-padding arguments
        `warmup` dispatches — for inspecting what the compiled serving
        step contains, e.g. its kernels in ``.compile().as_text()``."""
        if self._scheduler is None:
            self._scheduler = self._serving_init()
        if not self._scheduler.chunked:
            raise ValueError("the one-shot path has no chunk step")
        c = width or max(self._scheduler.width_buckets)
        b = self.num_slots
        tables = self._device_tables(self._context_bucket(self.max_seq - 1))
        return self._chunk_greedy.lower(
            self._params_run, self._paged_cache, tables,
            jnp.zeros((b, c), jnp.int32), jnp.full((b, c), -1, jnp.int32),
            jnp.zeros(b, jnp.int32), jnp.zeros(b, jnp.int32))

    def placement(self) -> dict[str, set]:
        """Devices holding the serving params and the page pools (the
        serving state exists after the first `submit` or `warmup`)."""
        if self._scheduler is None:
            self._scheduler = self._serving_init()

        def devs(tree):
            return {d for x in jax.tree.leaves(tree) for d in x.devices()}

        return {"params": devs(self._params_run),
                "pools": devs(self._paged_cache)}

    def _exec_prefill_commit(self, req: Request, slot: int,
                             pages: list[int], n_shared: int = 0) -> int:
        self._key, sub = jax.random.split(self._key)
        toks = jnp.asarray(req.tokens, jnp.int32)[None, :]
        tok, self._paged_cache = self._prefill_fused(
            self.params, self._paged_cache, toks, jnp.int32(slot),
            jnp.asarray(pages, jnp.int32),
            jnp.asarray(req.temperature, jnp.float32),
            jnp.asarray(req.top_k, jnp.int32), sub, n_shared)
        return int(tok)

    def _exec_decode(self, page_tables, token, pos, temps, topks
                     ) -> np.ndarray:
        st = self._scheduler.stats
        greedy = not temps.any() and not topks.any()
        with span(st, "serve.upload"):
            tables = self._device_tables(host_tables=page_tables)
            host = [token, pos] if greedy else [token, pos, temps, topks]
            args = [jnp.asarray(a) for a in host]
            if not greedy:
                self._key, sub = jax.random.split(self._key)
                args.append(sub)
        fn = self._decode_greedy if greedy else self._decode_paged
        with span(st, "serve.dispatch"):
            next_tok, self._paged_cache = fn(self.params, self._paged_cache,
                                             tables, *args)
        with span(st, "serve.wait"):
            return np.asarray(next_tok)

    def submit(self, tokens, max_new_tokens: int,
               sampler: SamplerConfig | None = None,
               eos_id: int | None = None,
               prefix_id: str | None = None,
               priority: int = 0, n: int = 1) -> int | list[int]:
        """Queue one request; returns its request id (or ``n`` ids).

        ``prefix_id`` opts the request into prefix sharing: requests
        carrying the same id alias any already-resident full KV pages
        whose token content matches their prompt's page-aligned prefix
        (typically a common system prompt), copy-on-write on the partial
        tail page. Greedy streams are token-identical with or without it.

        ``priority`` is the request's SLO class (higher = more urgent):
        admission strictly prefers higher classes, and with
        ``preemption=True`` a stalled higher class spills a lower-class
        victim's KV pages to the host tier and takes its slot; the victim
        restores later with zero recompute. Priorities reorder
        **scheduling**, never tokens — every stream stays identical to
        its uninterrupted run.

        ``n > 1`` requests parallel sampling: ``n`` continuations of the
        same prompt, returned as a list of request ids. The siblings
        share one prefix namespace (an auto-generated one when
        ``prefix_id`` is None), so the prompt's full KV pages are
        physically written once and aliased read-only by the other
        ``n - 1`` slots via the pager's refcounts; each slot
        copy-on-writes only its partial tail page when its own decode
        diverges. Greedy siblings emit identical streams; sampled
        siblings draw independently (one fresh key per dispatch).
        """
        if self._scheduler is None:
            self._scheduler = self._serving_init()
        if n < 1:
            raise ValueError(f"n must be >= 1, got {n}")
        s = sampler or self.sampler
        pid = prefix_id
        if n > 1 and pid is None:
            pid = f"__par{self._next_rid}"
        rids = []
        for _ in range(n):
            rid = self._next_rid
            self._next_rid += 1
            self._scheduler.submit(Request(
                rid=rid, tokens=np.asarray(tokens, np.int32).reshape(-1),
                max_new_tokens=max_new_tokens, temperature=s.temperature,
                top_k=s.top_k,
                eos_id=self.eos_id if eos_id is None else eos_id,
                prefix_id=pid, priority=priority))
            rids.append(rid)
        return rids if n > 1 else rids[0]

    def preempt(self, rid: int) -> bool:
        """Spill ``rid``'s slot to the host tier now (ops/test hook —
        organic preemption is priority-driven). False when ``rid`` holds
        no slot. Requires ``preemption=True``."""
        if self._scheduler is None:
            return False
        return self._scheduler.preempt_request(rid)

    def pin_prefix(self, prefix_id: str) -> int:
        """Keep ``prefix_id``'s indexed KV pages resident across bursts.

        Call while (or after) a request carrying the prefix is being
        served — the pin refcounts every page currently indexed under the
        namespace, plus any registered under it later, so the next burst
        aliases the prefix without recomputing its KV (under chunked
        prefill that skips the prefill FLOPs too). Returns the number of
        pages pinned now. Pinned pages count against the admission
        budget until `unpin_prefix` releases them.
        """
        if self._scheduler is None:
            self._scheduler = self._serving_init()
        return self._scheduler.pager.pin_prefix(prefix_id)

    def unpin_prefix(self, prefix_id: str) -> int:
        """Release a `pin_prefix` hold; unowned pages free exactly once."""
        if self._scheduler is None:
            return 0
        return self._scheduler.pager.unpin_prefix(prefix_id)

    def step(self) -> list[tuple[int, int]]:
        """One scheduler step → list of (rid, token) stream events."""
        if self._scheduler is None:
            return []
        return self._scheduler.step()

    def collect(self) -> dict[int, np.ndarray]:
        """Drain finished requests accumulated so far: {rid: tokens}."""
        if self._scheduler is None:
            return {}
        out = dict(self._scheduler.finished)
        self._scheduler.finished.clear()
        return out

    def drain(self) -> dict[int, np.ndarray]:
        """Step until queue + slots are empty; returns all finished."""
        if self._scheduler is None:
            return {}
        out = self.collect()
        out.update(self._scheduler.run())
        return out

    @property
    def idle(self) -> bool:
        """True when no requests are queued or in flight."""
        return self._scheduler is None or self._scheduler.idle

    @property
    def num_active(self) -> int:
        """Requests currently holding a decode slot."""
        return 0 if self._scheduler is None else self._scheduler.num_active

    @property
    def scheduler_stats(self):
        return self._scheduler.stats if self._scheduler else None

    # ------------------------------------------------------------- snapshot
    def stats(self) -> EngineStats:
        """One structured serving snapshot (see `EngineStats`).

        The public metrics surface: benchmarks and dashboards read THIS,
        not the scheduler's or pager's internal counters. Initializes the
        serving state lazily (like `submit`) so a fresh engine can be
        inspected before its first request.
        """
        if self._scheduler is None:
            self._scheduler = self._serving_init()
        st = self._scheduler.stats
        pool_total = pool_per_dev = 0
        for seg in self._paged_cache.values():
            pool = seg.get("kv_pool")
            if not pool:
                continue
            for a in pool.values():
                pool_total += int(np.prod(a.shape)) * a.dtype.itemsize
                shard = a.sharding.shard_shape(a.shape) \
                    if hasattr(a, "sharding") else a.shape
                pool_per_dev += int(np.prod(shard)) * a.dtype.itemsize
        model_axis = 1 if self._mesh is None \
            else int(self._mesh.shape.get("model", 1))
        pager_stats = self._scheduler.pager.stats()
        return EngineStats(
            pager=pager_stats,
            dispatches=st.decode_steps,
            prefill_tokens=st.prefill_tokens,
            prefill_tokens_skipped=st.prefill_tokens_skipped,
            prefix_shared_pages=st.prefix_shared_pages,
            padding_waste=st.padding_waste,
            acceptance_rate=st.acceptance_rate,
            spec_tokens_per_row=st.spec_tokens_per_row,
            draft_tokens=st.draft_tokens,
            accepted_tokens=st.accepted_tokens,
            rollbacks=st.rollbacks,
            spec_k_now=self._scheduler.spec_k_cur,
            spec_fanout_now=getattr(self._scheduler, "fanout_cur", 1),
            preemptions=st.preemptions,
            pressure_spills=st.pressure_spills,
            restores=st.restores,
            spilled_pages=st.spilled_pages,
            restored_pages=st.restored_pages,
            pages_spilled_now=pager_stats.pages_spilled,
            restore_ms_mean=(st.restore_time_s * 1e3
                             / max(st.restores, 1)),
            model_axis=model_axis,
            kv_pool_bytes=pool_total,
            kv_pool_bytes_per_device=pool_per_dev,
            kv_bytes_per_token=self.paged_kv_bytes_per_token(),
            weight_bytes=self.weight_stream_bytes(),
            weight_bytes_per_token=self.weight_bytes_per_token(
                st.spec_tokens_per_row),
            step_times=StepTimes(
                spans={n: (c, st.span_s[n], st.span_max_s[n])
                       for n, c in st.span_count.items()},
                kinds={k: (c, st.step_kind_s[k])
                       for k, c in st.step_kind_count.items()},
                queue_wait_s=st.queue_wait_s, queue_waits=st.queue_waits,
                prefill_s=st.prefill_s, prefills=st.prefills,
                pool_pages_drawn=st.pool_pages_drawn,
                pool_pages_usable=pager_stats.pages_total - 1,
                dispatches=st.decode_steps),
            queue_depth=(len(self._scheduler.queue)
                         + len(self._scheduler.preempted)),
            admission_headroom=max(
                0, pager_stats.pages_free - pager_stats.pages_reserved))

    def reset_stats(self) -> None:
        """Zero the cumulative counters behind `stats()` (occupancy and
        the adaptive ``spec_k`` state are live state, not counters, and
        are untouched) — benchmarks call this between warmup and the
        timed run.

        Resets **in place** via `SchedulerStats.zero()`: the stats object
        keeps its identity (held references stay live) and any field
        without a declared default — e.g. one a subclass binds at
        construction — survives, where rebuilding via ``type(stats)()``
        would raise or silently drop it.
        """
        if self._scheduler is not None:
            self._scheduler.stats.zero()

    def prefix_reuse_pages(self, tokens, prefix_id) -> int:
        """Exact count of already-resident KV pages a request with this
        prompt + ``prefix_id`` would alias instead of recomputing.

        This is the fleet router's affinity signal: the prefix index is
        content-addressed, so the count is exact — not an estimate. A
        fresh engine (serving never initialized) holds no pages and
        reports 0 without allocating anything.
        """
        if prefix_id is None or self._scheduler is None:
            return 0
        return len(self._scheduler.pager.match_prefix(tokens, prefix_id))

    # --------------------------------------------------- capacity accounting
    def paged_kv_page_bytes(self) -> int:
        """Bytes one physical page costs across all layers (codes + scale
        strips for int8 pools) — the unit of the serving memory budget.

        Pure shape accounting: when serving is not yet initialized the
        cache layout is traced with `jax.eval_shape`, so nothing is
        allocated on device.
        """
        if self._scheduler is not None:
            cache = self._paged_cache
            num_pages = self._scheduler.pager.cfg.num_pages
        else:
            pages_per_slot = self.max_seq // self.page_size
            num_pages = self._num_pages
            if num_pages is None:
                num_pages = self.num_slots * pages_per_slot + 1
            cache = jax.eval_shape(
                lambda: self.model.init_paged_cache(
                    self.num_slots, num_pages, self.page_size, self.max_seq,
                    kv_quant=self.kv_quant))
        total = 0
        for seg in cache.values():
            pool = seg.get("kv_pool")
            if pool:
                total += sum(int(np.prod(a.shape)) * a.dtype.itemsize
                             for a in pool.values())
        return total // num_pages

    def paged_kv_bytes_per_token(self) -> float:
        """KV bytes per cached token in the page pools (all layers)."""
        return self.paged_kv_page_bytes() / self.page_size

    def weight_stream_bytes(self) -> int:
        """Resident bytes of the served params — what ONE decode step
        streams through the matmul units. `PackedLinear` leaves count
        their int4 packing plus scales/zeros/input_scale metadata, so for
        the quantized model this is the paper's ~3.6× compression lever
        on the decode roofline."""
        from repro.utils.tree import leaf_bytes
        return leaf_bytes(self.params)

    def weight_bytes_per_token(self, spec_tokens_per_row: float = 0.0
                               ) -> float:
        """Weight bytes streamed per EMITTED token: the full weight pass,
        amortized over the tokens each decode row emits per dispatch
        (> 1 only under speculative decoding)."""
        return self.weight_stream_bytes() / max(spec_tokens_per_row, 1.0)

    def generate_scan(self, batch: dict, max_new_tokens: int, key=None):
        """Fixed-length scan generation (benchmark path, single dispatch)."""
        key = key if key is not None else jax.random.PRNGKey(0)
        b = next(iter(batch.values())).shape[0]
        cache = self.model.init_cache(b, self.max_seq)

        @self._exec_jit
        def run(params, batch, cache, key):
            cache, logits, pos = self.model.prefill(params, batch, cache)
            tok0 = sample(logits, self.sampler, key)

            def body(carry, _):
                tok, cache, pos, key = carry
                key, sub = jax.random.split(key)
                logits, cache = self.model.decode_step(params, cache, tok,
                                                       pos)
                nxt = sample(logits, self.sampler, sub)
                return (nxt, cache, pos + 1, key), tok

            (_, _, _, _), toks = jax.lax.scan(
                body, (tok0, cache, pos, key), None,
                length=max_new_tokens)
            return jnp.moveaxis(toks, 0, 1)

        return np.asarray(run(self.params, batch, cache, key))
