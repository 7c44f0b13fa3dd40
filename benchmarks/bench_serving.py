"""Serving benchmark: chunked token-budget serving vs. one-shot prefill
vs. the static batch.

The paper's 5.1 tok/s (§III) is a single-stream number; a serving system
cares about *sustained* throughput and time-to-first-token under
concurrent traffic. This bench replays Poisson-arrival workloads (mixed
prompt lengths, mixed token budgets) through three execution models:

  * **static batching** — requests are grouped in arrival order into
    fixed batches of ``num_slots``; each batch left-pads prompts to a
    common length and decodes until the *longest* budget in the batch is
    met (the classic convoy effect: short requests ride along as padding).
  * **one-shot continuous batching** — per-request admission runs a full
    dense prefill (jit per prompt length) fused with page commit and
    first-token sampling, then fixed-shape decode. The PR-2 baseline
    (``chunked_prefill=False``).
  * **chunked (token-budget) serving** — every step is ONE fixed-shape
    ``num_slots × prefill_chunk`` dispatch packing prefill chunks and
    decode tokens from mixed requests; exactly one compiled step
    function; aliased shared-prefix pages are read, never recomputed.

Reported: sustained tok/s (useful tokens / wall), per-request latency
p50/p95 (finish − arrival), **TTFT p50/p95** (first stream token −
arrival), decode-step counts, and **prefill-FLOPs-saved** accounting
(prompt tokens never run through the model thanks to prefix aliasing).
Also verifies that greedy chunked streams are token-identical to
per-request `generate()` — throughput must not come at the cost of
changed outputs.

Scenario sections:

  * **convoy** — a mixed long-prompt/short-prompt Poisson burst: under
    one-shot prefill a long prompt monopolizes the engine while admitted
    (short requests' decode stalls behind the dense prefill dispatch);
    chunked serving interleaves, fixing the convoy effect.
  * **KV quantization** — KV bytes/token with bf16 vs. int8 page pools,
    and the max concurrent slots a fixed page-pool byte budget holds.
  * **prefix sharing** — requests over one shared system prefix, served
    chunked vs. one-shot: with chunked prefill the aliased pages save
    *prefill FLOPs* (followers skip the whole prefix), not just memory —
    TTFT collapses accordingly.
  * **speculative decoding** — a repetitive-text burst (the prompt-lookup
    drafter's home turf) through `spec_decode="ngram"`: acceptance rate,
    mean tokens emitted per verify run (> 1 means one weight pass now
    amortizes over several tokens — the lever against the paper's
    memory-bandwidth-bound 5.1 tok/s decode), unified-dispatch count vs.
    the plain engine, and greedy token identity.
  * **decode-row packing** — every row of the unified dispatch declares
    its true run length and the packer pads only to the smallest width
    bucket covering the step; reported as the padding-waste % of
    dispatched positions.
  * **tiered SLO (preemption + KV spill)** — two overload shapes against
    the same engine, TTFT measured in *dispatch steps* (deterministic
    under greedy, so the smoke gate asserts improvements instead of
    eyeballing wall clock; wall-clock p95 reported alongside):
    *slot contention* — ``num_slots=2`` fully held by low-priority batch
    decodes when interactive requests arrive; without preemption they
    convoy behind a whole batch budget, with it the scheduler spills a
    victim's KV pages to the host tier and restores it later, holding
    interactive TTFT flat. *long-context reservation* — a long request's
    worst-case reservation blocks every short under conservative
    admission (the scaled-down 32k-convoy problem); optimistic admission
    admits them immediately and relieves pool pressure by spilling. The
    preempted streams are asserted token-identical to uninterrupted
    per-request `generate()` (gated identity section).
  * **mesh-sharded serving** — the full feature stack (chunked + int8 +
    prefix sharing + ngram spec) through ``GenerationEngine(mesh=...)``
    for every ``model``-axis size the host's devices allow: greedy
    streams must stay token-identical to the unsharded engine, and
    per-device peak page-pool bytes must shrink ~linearly with the axis
    (pools stripe over KV heads; page tables and the pager replicate).
    With one local device only the degenerate size-1 mesh runs — force
    more with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
  * **disaggregated prefill/decode** — ``DisaggController`` hands
    committed KV pages from a prefill engine to a decode engine with
    zero recompute: greedy streams stay token-identical to the unified
    engine (gated section, incl. a prefill-mesh ≠ decode-mesh leg when
    devices allow), decode-side TTFT is reported as pure transfer cost
    (wire KiB + adopt ms per handoff), and a mixed burst scores the
    convoy effect on the decode-side clock next to the roofline
    report's predicted disaggregation crossover.
  * **multi-replica fleet (router)** — N engines behind the
    prefix-affinity `Router`: a clustered-prefix Poisson burst served
    with affinity placement vs. seeded-random placement (affinity must
    skip strictly more prefill tokens; sustained tok/s is asserted at
    full scale and reported at smoke scale), sustained throughput vs.
    replica count {1, 2, 4}, a 1-replica fleet asserted token-identical
    to the bare engine (gated section ``router_vs_single``), and an
    elastic `drain_replica` under load that must lose and duplicate
    nothing (every stream checked against bare-engine references).

All metrics come from the engine's public `stats()` snapshot — the bench
never reaches into scheduler or pager internals. Every **asserted
identity section** registers itself in ``identity_sections``; the run
exits non-zero if any registered-expected section is missing or False,
so the smoke gate cannot silently pass while covering nothing.

Runs end-to-end on CPU at smoke scale (pure JAX path; no TPU kernels).
``--smoke`` runs a reduced version as the tier-1 end-to-end gate.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import subprocess
import sys
import time

import jax
import numpy as np

import repro.configs as C
from repro.distributed import serving_mesh
from repro.models import build_model
from repro.roofline.costmodel import disagg_report
from repro.serving import DisaggController, GenerationEngine, Router

# identity sections the gate requires: each section sets its key to the
# asserted comparison's outcome only after ACTUALLY running it — a
# section that is skipped (or crashes) leaves its key missing, and
# `main` exits non-zero either way
REQUIRED_IDENTITY = ("chunked_vs_oneshot_vs_generate", "spec_vs_plain",
                     "sharded_vs_unsharded", "awq_kernel_vs_ref",
                     "preempt_vs_uninterrupted", "tree_vs_plain",
                     "parallel_vs_single", "disagg_vs_unified",
                     "router_vs_single")

NUM_REQUESTS = 16
NUM_SLOTS = 4
PAGE_SIZE = 8
MAX_SEQ = 160
PREFILL_CHUNK = 16
ARRIVAL_RATE = 200.0       # req/s — burst load: offered load > capacity,
                           # so throughput measures the engine, not arrivals
PROMPT_LENS = (6, 10, 14, 18)
# long and short budgets interleaved, as a Poisson trace would deliver
# them — each static batch convoy-waits on one long request
TOKEN_BUDGETS = (72, 6, 8, 6, 64, 12, 8, 6, 48, 8, 6, 12, 36, 6, 8, 12)


def make_workload(cfg, seed=0, num_requests=NUM_REQUESTS,
                  prompt_lens=PROMPT_LENS, budgets=TOKEN_BUDGETS,
                  rate=ARRIVAL_RATE):
    """(arrival_s, prompt, max_new) triples, Poisson arrivals, mixed sizes."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, num_requests))
    reqs = []
    for i in range(num_requests):
        n = prompt_lens[i % len(prompt_lens)]
        prompt = rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
        reqs.append((float(arrivals[i]), prompt, int(budgets[i % len(budgets)])))
    return reqs


def _fresh_engine(m, params, **kw):
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("num_slots", NUM_SLOTS)
    kw.setdefault("page_size", PAGE_SIZE)
    kw.setdefault("prefill_chunk", PREFILL_CHUNK)
    return GenerationEngine(m, params, **kw)


# ---------------------------------------------------------------------------
# Static-batch baseline
# ---------------------------------------------------------------------------

def _pad_batch(prompts):
    """Left-pad to a common length (keeps the last prompt token last)."""
    s = max(len(p) for p in prompts)
    out = np.zeros((len(prompts), s), np.int32)
    for i, p in enumerate(prompts):
        out[i, s - len(p):] = p
    return out


def run_static(eng, workload):
    """Arrival-order batches of NUM_SLOTS; returns (tokens, lat, steps, dt)."""
    batches = [workload[i:i + NUM_SLOTS]
               for i in range(0, len(workload), NUM_SLOTS)]
    # warmup: compile prefill/decode for every padded batch shape
    for batch in batches:
        eng.generate({"tokens": _pad_batch([p for _, p, _ in batch])}, 2)
    t0 = time.perf_counter()
    latencies, useful, steps = [], 0, 0
    for batch in batches:
        run_until = max(mn for _, _, mn in batch)
        last_arrival = max(a for a, _, _ in batch)
        # convoy admission: the batch cannot launch before its last arrival
        while time.perf_counter() - t0 < last_arrival:
            time.sleep(0.0005)
        eng.generate({"tokens": _pad_batch([p for _, p, _ in batch])},
                     run_until)
        steps += run_until
        done = time.perf_counter() - t0
        for arrival, _, mn in batch:
            latencies.append(done - arrival)
            useful += mn
    return useful, latencies, steps, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# Continuous batching (one-shot or chunked, per engine flags)
# ---------------------------------------------------------------------------

def run_continuous(eng, workload, prefix_id=None):
    """Replay a workload; returns (useful, latencies, ttfts, steps, dt).

    ``latencies`` are finish − arrival, ``ttfts`` first-token − arrival,
    both in request order.
    """
    # warmup. Chunked path: `warmup()` precompiles the full bounded step
    # family (context buckets × block widths). One-shot path: compile
    # every prompt length the workload will present; with a prefix_id,
    # also run the first two real prompts back to back — they share
    # exactly the workload's prefix, so the aliased-commit variant
    # (static start_page = shared pages) compiles before the timed run.
    eng.warmup()
    if not eng._scheduler.chunked:
        seen = set()
        for _, prompt, _ in workload:
            if len(prompt) not in seen:
                seen.add(len(prompt))
                eng.submit(prompt, 2, prefix_id=prefix_id)
        if prefix_id is not None and len(workload) > 1:
            # the leader registers its prefix synchronously at admission,
            # so a follower queued behind it matches the real page count
            eng.submit(workload[1][1], 2, prefix_id=prefix_id)
    eng.drain()
    eng.reset_stats()                   # timed run reports clean stats
    pending = sorted(enumerate(workload), key=lambda r: r[1][0])
    finish: dict[int, float] = {}
    first: dict[int, float] = {}
    last_tok: dict[int, float] = {}
    itl_max: dict[int, float] = {}     # worst inter-token gap (decode stall)
    arrival_of: dict[int, float] = {}
    t0 = time.perf_counter()
    i = 0
    while True:
        now = time.perf_counter() - t0
        while i < len(pending) and pending[i][1][0] <= now:
            _, (arrival, prompt, mn) = pending[i]
            rid = eng.submit(prompt, mn, prefix_id=prefix_id)
            arrival_of[rid] = arrival
            i += 1
        events = eng.step()
        now = time.perf_counter() - t0
        for rid, _tok in events:
            if rid not in arrival_of:
                continue
            if rid not in first:
                first[rid] = now
            else:
                itl_max[rid] = max(itl_max.get(rid, 0.0),
                                   now - last_tok[rid])
            last_tok[rid] = now
        for rid in eng.collect():
            finish[rid] = now
        if len(finish) == len(workload):
            break
        if i < len(pending) and eng.idle:
            time.sleep(0.0005)
    dt = time.perf_counter() - t0
    useful = sum(mn for _, _, mn in workload)
    return {"useful": useful,
            "latencies": [finish[r] - arrival_of[r] for r in sorted(finish)],
            "ttfts": [first[r] - arrival_of[r] for r in sorted(first)],
            "itl_max": [itl_max.get(r, 0.0) for r in sorted(finish)],
            "steps": eng.stats().dispatches, "dt": dt}


# ---------------------------------------------------------------------------
# Convoy scenario: mixed long-prompt/short-prompt Poisson burst
# ---------------------------------------------------------------------------

CONVOY_LONG = 1024
CONVOY_SHORT = 6
CONVOY_MAX_SEQ = 1088


def make_convoy_workload(cfg, seed=2, num_requests=12, long_every=3,
                         rate=300.0):
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, num_requests))
    reqs = []
    for i in range(num_requests):
        if i % long_every == 0:
            n, mn = CONVOY_LONG, 6
        else:
            n, mn = CONVOY_SHORT, 24
        prompt = rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
        reqs.append((float(arrivals[i]), prompt, mn))
    return reqs


def run_convoy(m, params, csv_rows, num_requests=12):
    """Mixed long/short Poisson burst. Under one-shot prefill every
    long-prompt admission is a monolithic dense-prefill dispatch the
    whole engine waits on: short requests queued behind it pay its full
    prefill in TTFT, and in-flight decodes stall (completion latency).
    Chunked serving interleaves, so short-request latency decouples from
    long prompts."""
    wl = make_convoy_workload(m.cfg, num_requests=num_requests)
    res = {}
    for tag, kw in (("chunked", {"prefill_chunk": 64}),
                    ("oneshot", {"chunked_prefill": False})):
        eng = _fresh_engine(m, params, max_seq=CONVOY_MAX_SEQ, **kw)
        r = run_continuous(eng, wl)
        is_short = [len(p) == CONVOY_SHORT for _, p, _ in wl]
        short_ttft = [t for t, s in zip(r["ttfts"], is_short) if s]
        short_stall = [t for t, s in zip(r["itl_max"], is_short) if s]
        res[tag] = {"tps": r["useful"] / r["dt"],
                    "ttft_p95": float(np.percentile(r["ttfts"], 95)),
                    "short_ttft_p95": float(np.percentile(short_ttft, 95)),
                    "short_stall_max": float(np.max(short_stall)),
                    "p95": float(np.percentile(r["latencies"], 95))}
    csv_rows.extend([
        ("serving/convoy_tps_chunked", f"{res['chunked']['tps']:.1f}",
         f"{num_requests} reqs, {CONVOY_LONG}/{CONVOY_SHORT}-token prompts"),
        ("serving/convoy_tps_oneshot", f"{res['oneshot']['tps']:.1f}", ""),
        ("serving/convoy_short_ttft_p95_chunked_s",
         f"{res['chunked']['short_ttft_p95']:.3f}",
         "short requests queued behind long prefills"),
        ("serving/convoy_short_ttft_p95_oneshot_s",
         f"{res['oneshot']['short_ttft_p95']:.3f}", ""),
        ("serving/convoy_decode_stall_chunked_s",
         f"{res['chunked']['short_stall_max']:.3f}",
         "worst inter-token gap of a short request (the convoy effect)"),
        ("serving/convoy_decode_stall_oneshot_s",
         f"{res['oneshot']['short_stall_max']:.3f}",
         "decode waits out the whole monolithic long prefill"),
    ])
    return {"convoy": res}


# ---------------------------------------------------------------------------
# KV quantization: bytes/token + slots at a fixed page-pool budget
# ---------------------------------------------------------------------------

# the fixed-budget scenario: serve 512-token-context requests out of a
# 32 MiB page pool (the kind of budget an on-device accelerator has left
# after the INT4 weights)
BUDGET_BYTES = 32 * 1024 * 1024
BUDGET_CONTEXT = 512


def run_kv_quant(m, params, csv_rows):
    bpt = {}
    for quant in ("none", "int8"):
        eng = _fresh_engine(m, params, kv_quant=quant)
        bpt[quant] = eng.paged_kv_bytes_per_token()
    reduction = 1.0 - bpt["int8"] / bpt["none"]
    pages_per_req = -(-BUDGET_CONTEXT // PAGE_SIZE)
    slots = {q: int(BUDGET_BYTES // (bpt[q] * PAGE_SIZE)) // pages_per_req
             for q in bpt}
    csv_rows.extend([
        ("serving/kv_bytes_per_token_bf16", f"{bpt['none']:.0f}",
         "page-pool bytes per cached token, all layers"),
        ("serving/kv_bytes_per_token_int8", f"{bpt['int8']:.0f}",
         "int8 codes + f32 scale strips"),
        ("serving/kv_bytes_reduction", f"{reduction:.1%}",
         "int8 vs bf16 pages (target ≥ 40%)"),
        ("serving/slots_at_32MiB_bf16", str(slots["none"]),
         f"{BUDGET_CONTEXT}-token contexts in a 32 MiB pool"),
        ("serving/slots_at_32MiB_int8", str(slots["int8"]),
         f"{slots['int8'] / max(slots['none'], 1):.1f}x the bf16 slots"),
    ])
    return {"kv_bytes_per_token": bpt, "kv_bytes_reduction": reduction,
            "budget_slots": slots}


# ---------------------------------------------------------------------------
# Prefix sharing: a burst over one shared system prefix, chunked vs one-shot
# ---------------------------------------------------------------------------

PREFIX_LEN = 512
PREFIX_REQUESTS = 8
PREFIX_TAIL = 16
PREFIX_NEW_TOKENS = 32


def _prefix_workload(cfg, seed=4, prefix_len=PREFIX_LEN,
                     num_requests=PREFIX_REQUESTS, tail=PREFIX_TAIL,
                     new_tokens=PREFIX_NEW_TOKENS, rate=400.0):
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, (prefix_len,)).astype(np.int32)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, num_requests))
    return [(float(arrivals[i]),
             np.concatenate([prefix,
                             rng.integers(0, cfg.vocab_size, (tail,)
                                          ).astype(np.int32)]),
             new_tokens)
            for i in range(num_requests)]


def run_prefix_sharing(m, params, csv_rows, prefix_len=PREFIX_LEN,
                       num_requests=PREFIX_REQUESTS,
                       new_tokens=PREFIX_NEW_TOKENS):
    wl = _prefix_workload(m.cfg, prefix_len=prefix_len,
                          num_requests=num_requests, new_tokens=new_tokens)
    max_seq = prefix_len + PREFIX_TAIL + new_tokens + PAGE_SIZE
    max_seq += -max_seq % PAGE_SIZE
    total_prompt = sum(len(p) for _, p, _ in wl)

    def serve(prefix_id, **kw):
        eng = _fresh_engine(m, params, max_seq=max_seq,
                            num_slots=num_requests, **kw)
        r = run_continuous(eng, wl, prefix_id=prefix_id)
        st = eng.stats()
        return {"tps": r["useful"] / r["dt"],
                "ttft_p95": float(np.percentile(r["ttfts"], 95)),
                "prefill_tokens": st.prefill_tokens,
                "skipped": st.prefill_tokens_skipped,
                "aliased_pages": st.prefix_shared_pages}

    shared_c = serve("sys")                         # chunked + prefix-aware
    shared_o = serve("sys", chunked_prefill=False)  # one-shot: memory only
    plain_c = serve(None)                           # chunked, no sharing
    flops_saved = shared_c["skipped"] / max(total_prompt, 1)
    csv_rows.extend([
        ("serving/prefix_shared_tps_chunked", f"{shared_c['tps']:.1f}",
         f"{num_requests} reqs × {prefix_len}-token shared prefix"),
        ("serving/prefix_shared_tps_oneshot", f"{shared_o['tps']:.1f}",
         "sharing saves memory but not FLOPs here"),
        ("serving/prefix_unshared_tps_chunked", f"{plain_c['tps']:.1f}", ""),
        ("serving/prefix_prefill_tokens_skipped", str(shared_c["skipped"]),
         f"{shared_c['aliased_pages']} aliased pages never recomputed"),
        ("serving/prefix_prefill_flops_saved", f"{flops_saved:.1%}",
         "prompt tokens skipped / total prompt tokens"),
        ("serving/prefix_ttft_p95_chunked_s", f"{shared_c['ttft_p95']:.3f}",
         "followers skip the whole prefix"),
        ("serving/prefix_ttft_p95_oneshot_s", f"{shared_o['ttft_p95']:.3f}",
         "followers re-run the full dense prefill"),
    ])
    return {"prefix_chunked": shared_c, "prefix_oneshot": shared_o,
            "prefix_unshared": plain_c, "prefix_flops_saved": flops_saved}


# ---------------------------------------------------------------------------
# Speculative decoding: repetitive-text burst, n-gram self-drafting
# ---------------------------------------------------------------------------

SPEC_K = 4
SPEC_NEW_TOKENS = 32


def make_repetitive_workload(cfg, seed=6, num_requests=8, pat_len=4,
                             reps=8, new_tokens=SPEC_NEW_TOKENS, rate=400.0):
    """Templated/repetitive prompts: each is a short pattern tiled, the
    regime prompt-lookup drafting exists for (code, lists, boilerplate)."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, num_requests))
    reqs = []
    for i in range(num_requests):
        pat = rng.integers(0, cfg.vocab_size, (pat_len,)).astype(np.int32)
        reqs.append((float(arrivals[i]), np.tile(pat, reps),
                     int(new_tokens)))
    return reqs


def run_spec(m, params, csv_rows, identity, num_requests=8,
             new_tokens=SPEC_NEW_TOKENS, k=SPEC_K,
             tag_prefix="serving/spec"):
    """Repetitive burst through the n-gram speculative engine vs. the
    plain chunked engine: same streams (greedy identity is asserted),
    fewer weight passes."""
    wl = make_repetitive_workload(m.cfg, num_requests=num_requests,
                                  new_tokens=new_tokens)
    max_seq = max(len(p) for _, p, _ in wl) + new_tokens
    max_seq += -max_seq % PAGE_SIZE
    res = {}
    streams = {}
    for tag, kw in (("spec", {"spec_decode": "ngram", "spec_k": k}),
                    ("plain", {})):
        eng = _fresh_engine(m, params, max_seq=max_seq, **kw)
        r = run_continuous(eng, wl)
        st = eng.stats()
        res[tag] = {"tps": r["useful"] / r["dt"], "steps": r["steps"],
                    "acceptance": st.acceptance_rate,
                    "tokens_per_step": st.spec_tokens_per_row,
                    "drafted": st.draft_tokens,
                    "accepted": st.accepted_tokens,
                    "rollbacks": st.rollbacks}
        # identity replay: drain the same prompts through a fresh engine
        eng2 = _fresh_engine(m, params, max_seq=max_seq, **kw)
        rids = [eng2.submit(p, mn) for _, p, mn in wl]
        out = eng2.drain()
        streams[tag] = [list(out[r_]) for r_ in rids]
    identical = streams["spec"] == streams["plain"]
    res["identical"] = identical
    identity["spec_vs_plain"] = identical
    csv_rows.extend([
        (f"{tag_prefix}_acceptance_rate",
         f"{res['spec']['acceptance']:.1%}",
         f"{res['spec']['accepted']}/{res['spec']['drafted']} drafts "
         f"accepted (ngram, k={k})"),
        (f"{tag_prefix}_tokens_per_step",
         f"{res['spec']['tokens_per_step']:.2f}",
         "tokens emitted per verify run (1.0 = drafting never helped)"),
        (f"{tag_prefix}_dispatches", str(res["spec"]["steps"]),
         f"vs {res['plain']['steps']} without drafting — each dispatch "
         f"is one weight pass"),
        (f"{tag_prefix}_tps", f"{res['spec']['tps']:.1f}",
         f"plain chunked: {res['plain']['tps']:.1f}"),
        (f"{tag_prefix}_rollbacks", str(res["spec"]["rollbacks"]),
         "verify runs that truncated the KV watermark"),
        (f"{tag_prefix}_token_identity", str(identical),
         "greedy spec streams ≡ plain chunked streams"),
    ])
    return res


TREE_FANOUT = 2
PARALLEL_N = 3


def run_tree_spec(m, params, csv_rows, identity, num_requests=8,
                  new_tokens=SPEC_NEW_TOKENS, k=SPEC_K,
                  tag_prefix="serving/tree"):
    """Tree speculation vs. linear speculation vs. plain decode.

    Two bursts:

    * the repetitive burst through the n-gram drafters — the tree
      drafter proposes the primary chain plus depth-1 alternate first
      tokens from older occurrence sites. Greedy tree streams are
      asserted token-identical to the plain chunked engine (the gated
      ``tree_vs_plain`` identity section).
    * a *branchy* burst through a two-hypothesis hedged drafter that
      backs the wrong branch on two verify passes out of three — the
      regime hedging exists for. The linear drafter must commit to one
      branch and loses its whole chain on a wrong guess; the tree
      spends one node on the rival branch and salvages an accepted
      token from the same weight pass, so it finishes the same streams
      in strictly fewer dispatches (bench-asserted in ``__main__``).
    """
    import jax.numpy as jnp
    wl = make_repetitive_workload(m.cfg, num_requests=num_requests,
                                  new_tokens=new_tokens)
    max_seq = max(len(p) for _, p, _ in wl) + new_tokens
    max_seq += -max_seq % PAGE_SIZE
    res: dict = {}
    streams: dict = {}
    for tag, kw in (
            ("tree", {"spec_decode": "ngram", "spec_k": k,
                      "spec_tree": True, "spec_tree_fanout": TREE_FANOUT}),
            ("linear", {"spec_decode": "ngram", "spec_k": k}),
            ("plain", {})):
        eng = _fresh_engine(m, params, max_seq=max_seq, **kw)
        rids = [eng.submit(p, mn) for _, p, mn in wl]
        out = eng.drain()
        st = eng.stats()
        streams[tag] = [list(out[r]) for r in rids]
        res[tag] = {"steps": st.dispatches,
                    "acceptance": st.acceptance_rate,
                    "tokens_per_step": st.spec_tokens_per_row,
                    "drafted": st.draft_tokens,
                    "accepted": st.accepted_tokens,
                    "rollbacks": st.rollbacks,
                    "fanout_now": st.spec_fanout_now}
    identical = streams["tree"] == streams["plain"]
    res["identical"] = identical
    identity["tree_vs_plain"] = identical

    # branchy burst: the drafter knows the continuation but hedges an
    # uncertain first token (a branch point). Reference streams come from
    # generate(), so both engines' drafters see the same two hypotheses.
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, m.cfg.vocab_size, (8,)).astype(np.int32)
               for _ in range(min(num_requests, NUM_SLOTS))]
    ref_eng = _fresh_engine(m, params, max_seq=max_seq)
    refs = [np.asarray(ref_eng.generate({"tokens": jnp.asarray(p)[None, :]},
                                        new_tokens)[0])
            for p in prompts]

    def _hedged(tree, oracle):
        calls: dict = {}

        def draft(reqs):
            out = {}
            for req in reqs:
                slot, rid, ctx, kk = req[0], req[1], req[2], req[4]
                ref, plen = oracle[rid]
                done = len(ctx) - plen
                true = [int(t) for t in ref[done:done + kk]]
                if not true:
                    continue
                i = calls.get(rid, 0)
                calls[rid] = i + 1
                rival = (true[0] + 1) % m.cfg.vocab_size
                wrong = i % 3 != 2          # backs the wrong branch 2/3
                first = rival if wrong else true[0]
                if tree:
                    nodes = [(first, -1)]
                    nodes += [(t, j) for j, t in enumerate(true[1:kk - 1])]
                    if kk > 1:              # hedge: the rival first token
                        nodes.append((true[0] if wrong else rival, -1))
                    out[slot] = nodes
                else:
                    out[slot] = [first] + true[1:kk]
            return out
        return draft

    branchy: dict = {}
    bstreams: dict = {}
    for tag, tree in (("tree", True), ("linear", False)):
        oracle: dict = {}
        kw = {"spec_decode": "draft_model", "spec_k": k,
              "draft_fn": _hedged(tree, oracle)}
        if tree:
            kw |= {"spec_tree": True, "spec_tree_fanout": TREE_FANOUT}
        eng = _fresh_engine(m, params, max_seq=max_seq, **kw)
        rids = []
        for p, ref in zip(prompts, refs):
            rid = eng.submit(p, new_tokens)
            oracle[rid] = (ref, len(p))
            rids.append(rid)
        out = eng.drain()
        st = eng.stats()
        bstreams[tag] = [list(out[r]) for r in rids]
        branchy[tag] = {"steps": st.dispatches,
                        "acceptance": st.acceptance_rate,
                        "tokens_per_step": st.spec_tokens_per_row}
    assert bstreams["tree"] == bstreams["linear"]
    for s, ref in zip(bstreams["tree"], refs):
        np.testing.assert_array_equal(s, ref[: len(s)])
    res["branchy"] = branchy
    csv_rows.extend([
        (f"{tag_prefix}_acceptance_rate",
         f"{res['tree']['acceptance']:.1%}",
         f"{res['tree']['accepted']}/{res['tree']['drafted']} tree nodes "
         f"accepted (ngram chain+alternates, k={k})"),
        (f"{tag_prefix}_tokens_per_pass",
         f"{res['tree']['tokens_per_step']:.2f}",
         f"vs {res['linear']['tokens_per_step']:.2f} linear — tokens per "
         f"verify weight pass, repetitive burst"),
        (f"{tag_prefix}_dispatches", str(res["tree"]["steps"]),
         f"vs {res['linear']['steps']} linear / "
         f"{res['plain']['steps']} plain"),
        (f"{tag_prefix}_fanout_now", str(res["tree"]["fanout_now"]),
         "adaptive root fanout after the burst (1 = chain only)"),
        (f"{tag_prefix}_branchy_dispatches", str(branchy["tree"]["steps"]),
         f"vs {branchy['linear']['steps']} linear — hedged drafter wrong "
         f"on 2/3 of passes; the depth-1 hedge must win"),
        (f"{tag_prefix}_token_identity", str(identical),
         "greedy tree-spec streams ≡ plain chunked streams"),
    ])
    return res


def run_parallel(m, params, csv_rows, identity, n=PARALLEL_N,
                 prompt_len=32, new_tokens=16,
                 tag_prefix="serving/parallel"):
    """``submit(n=…)`` parallel sampling: ``n`` continuations of one
    prompt alias its physical prompt pages (refcounted, copy-on-write
    partial tail) instead of prefilling and storing ``n`` copies.
    Greedy siblings are asserted identical to ``n`` independent
    submissions (the gated ``parallel_vs_single`` identity section);
    the physical-page and prefill-FLOP savings are reported."""
    rng = np.random.default_rng(17)
    prompt = rng.integers(0, m.cfg.vocab_size,
                          (prompt_len,)).astype(np.int32)
    eng_sep = _fresh_engine(m, params)
    rids = [eng_sep.submit(prompt, new_tokens) for _ in range(n)]
    out = eng_sep.drain()
    sep = [list(out[r]) for r in rids]
    st_sep = eng_sep.stats()
    eng_par = _fresh_engine(m, params)
    rids = eng_par.submit(prompt, new_tokens, n=n)
    out = eng_par.drain()
    par = [list(out[r]) for r in rids]
    st_par = eng_par.stats()
    identical = par == sep
    identity["parallel_vs_single"] = identical
    shared = st_par.prefix_shared_pages
    page_bytes = eng_par.paged_kv_page_bytes()
    csv_rows.extend([
        (f"{tag_prefix}_shared_pages", str(shared),
         f"physical prompt pages aliased across {n} siblings "
         f"(vs {st_sep.prefix_shared_pages} with {n} separate submits)"),
        (f"{tag_prefix}_kv_bytes_saved", str(shared * page_bytes),
         f"{page_bytes} B/page × {shared} pages never duplicated"),
        (f"{tag_prefix}_prefill_tokens_skipped",
         str(st_par.prefill_tokens_skipped),
         f"vs {st_sep.prefill_tokens_skipped} unshared — aliased prompt "
         f"tokens never re-run through the weights"),
        (f"{tag_prefix}_token_identity", str(identical),
         f"greedy submit(n={n}) streams ≡ {n} independent submissions"),
    ])
    return {"identical": identical, "shared_pages": shared,
            "sep_shared": st_sep.prefix_shared_pages,
            "skipped": st_par.prefill_tokens_skipped,
            "kv_bytes_saved": shared * page_bytes}


def verify_token_identity(m, params, workload, identity):
    """Greedy chunked streams ≡ one-shot streams ≡ per-request generate()."""
    import jax.numpy as jnp
    eng = _fresh_engine(m, params)
    eng_one = _fresh_engine(m, params, chunked_prefill=False)
    rids = [eng.submit(p, mn) for _, p, mn in workload]
    rids_one = [eng_one.submit(p, mn) for _, p, mn in workload]
    out, out_one = eng.drain(), eng_one.drain()
    for rid, rid_one, (_, p, mn) in zip(rids, rids_one, workload):
        np.testing.assert_array_equal(out[rid], out_one[rid_one])
        ref = eng.generate({"tokens": jnp.asarray(p)[None, :]}, mn)[0]
        np.testing.assert_array_equal(out[rid], ref[: len(out[rid])])
    identity["chunked_vs_oneshot_vs_generate"] = True
    return True


def _padding_rows(st, csv_rows, tag="serving/padding"):
    """Decode-row packing accounting from a burst's `EngineStats`: rows
    declare their true run length, so padding is paid only up to the
    step's width bucket."""
    waste = st.padding_waste
    csv_rows.append(
        (f"{tag}_waste", f"{waste:.1%}",
         "share of dispatched positions holding padding "
         "(run-length packer)"))
    return {"waste": waste}


# ---------------------------------------------------------------------------
# Mesh-sharded serving: identity + per-device pool bytes vs the model axis
# ---------------------------------------------------------------------------

SHARD_PREFIX_LEN = 16
SHARD_NEW_TOKENS = 10


def run_sharded(csv_rows, identity):
    """The full serving feature stack under every ``model``-axis size the
    local devices allow (1 is the degenerate mesh — always runs, so this
    section can never be silently skipped): greedy streams must match
    the unsharded engine token-for-token while per-device page-pool
    bytes shrink with the axis. Uses an Hkv = 4 variant of the smoke
    config — pools shard over KV heads, so Hkv must divide the axis
    (that requirement is enforced at engine construction)."""
    cfg = dataclasses.replace(C.get_smoke_config("qwen25-05b"),
                              num_heads=8, num_kv_heads=4, head_dim=16)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(11)
    prefix = rng.integers(0, cfg.vocab_size,
                          (SHARD_PREFIX_LEN,)).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.integers(0, cfg.vocab_size, (t,)
                                            ).astype(np.int32)])
               for t in (5, 12, 9, 3)]
    sizes = [s for s in (1, 2, 4)
             if s <= jax.device_count() and cfg.num_kv_heads % s == 0]

    def serve(mesh):
        eng = GenerationEngine(m, params, max_seq=64, num_slots=4,
                               page_size=8, prefill_chunk=4,
                               kv_quant="int8", spec_decode="ngram",
                               spec_k=4, mesh=mesh)
        rids = [eng.submit(p, SHARD_NEW_TOKENS, prefix_id="sys")
                for p in prompts]
        out = eng.drain()
        return [list(out[r]) for r in rids], eng.stats()

    ref, st0 = serve(None)
    bytes_per_dev = {}
    identical = True
    for size in sizes:
        got, st = serve(serving_mesh(size))
        identical &= got == ref
        bytes_per_dev[size] = st.kv_pool_bytes_per_device
        csv_rows.append(
            (f"serving/sharded_kv_pool_bytes_per_device_model{size}",
             str(st.kv_pool_bytes_per_device),
             f"of {st.kv_pool_bytes} global pool bytes "
             f"({st.kv_pool_bytes / max(st.kv_pool_bytes_per_device, 1):.1f}"
             f"x reduction)"))
    shrink = bytes_per_dev[sizes[0]] / max(bytes_per_dev[sizes[-1]], 1)
    identity["sharded_vs_unsharded"] = identical
    csv_rows.extend([
        ("serving/sharded_axis_sizes", "/".join(map(str, sizes)),
         f"{jax.device_count()} local devices (force more with "
         f"XLA_FLAGS=--xla_force_host_platform_device_count=4)"),
        ("serving/sharded_token_identity", str(identical),
         "greedy sharded streams ≡ unsharded (chunked+int8+prefix+spec)"),
        ("serving/sharded_per_device_shrink",
         f"{shrink:.1f}x",
         f"pool bytes/device, model={sizes[0]} vs model={sizes[-1]}"),
    ])
    return {"identical": identical, "sizes": sizes,
            "bytes_per_device": bytes_per_dev}


# ---------------------------------------------------------------------------
# Disaggregated prefill/decode: zero-recompute KV page handoff
# ---------------------------------------------------------------------------

DISAGG_KW = dict(max_seq=96, num_slots=4, page_size=8, prefill_chunk=8,
                 kv_quant="int8", spec_decode="ngram", spec_k=4)
DISAGG_LONG = 80          # convoy prompt: handed off, never decodes on the
DISAGG_SHORT = 6          # prefill side; shorts route direct to decode
DISAGG_NEW = 8


def _disagg_warm(server, long_prompt, short_prompt):
    """Compile every shape the timed burst will hit (prefill lengths,
    decode widths, and — for the controller — the handoff gather/scatter
    movers and the adopted-slot decode), then zero the stats."""
    server.submit(short_prompt, 2)
    server.submit(long_prompt, 2)
    server.drain()
    server.reset_stats()


def _disagg_burst(server, shorts, new, longs, long_new, *,
                  decode_clock, delays=(3, 10)):
    """Replay a mixed burst and score it on the DECODE-side clock.

    On one host the two engines take turns, so wall time can't show the
    disaggregation win — what a separate decode accelerator would feel
    is the time spent inside *decode-side dispatches*. For a unified
    engine that clock IS its step clock (the long request's prefill runs
    in its dispatches); for the controller it is the decode-engine step
    time its stats already accumulate — prefill-engine dispatches never
    touch it. Each long prompt arrives a few steps in, once the shorts
    are mid-decode, and ``stall`` is the worst decode-clock gap between
    a short's consecutive tokens — the convoy effect as the decode
    accelerator experiences it, sampled once per long admission.
    """
    role = {}
    for p in shorts:
        role[server.submit(p, new)] = "short"
    total = len(shorts) + len(longs)
    arrive = dict(zip(delays, longs))
    wall_acc = 0.0
    last: dict = {}
    stall, toks, steps = 0.0, 0, 0
    done: set = set()
    clk = 0.0
    clk0 = server.stats().decode_step_time_s if decode_clock else 0.0
    while len(done) < total:
        if steps in arrive:
            role[server.submit(arrive.pop(steps), long_new)] = "long"
        steps += 1
        t0 = time.perf_counter()
        events = server.step()
        wall_acc += time.perf_counter() - t0
        clk = (server.stats().decode_step_time_s - clk0) if decode_clock \
            else wall_acc
        for rid, _tok in events:
            if role.get(rid) != "short":
                continue
            if rid in last:
                stall = max(stall, clk - last[rid])
                toks += 1
            last[rid] = clk
        done |= set(server.collect())
    return {"stall": stall, "decode_s": clk, "decode_toks": toks}


def run_disagg(csv_rows, identity, smoke=False):
    """`DisaggController` vs the unified engine, three claims:

      * **identity** (gated section) — greedy streams through the
        prefill→handoff→decode path match the unified engine token for
        token, with the full decode feature stack on (chunked + int8 KV
        + prefix sharing + ngram spec); with ≥ 2 local devices the same
        burst also runs with the decode engine on a 2-way ``model`` mesh
        while prefill stays unsharded — prefill mesh ≠ decode mesh, the
        replicated wire image doing the resharding.
      * **TTFT as transfer cost** — the decode side never re-runs
        prefill, so its time-to-first-token is the handoff itself: wire
        KiB and adopt milliseconds per handoff (int8 pools ship codes +
        scale strips, ~2× fewer bytes than bf16).
      * **convoy relief** — under a mixed burst (one long prompt + 3
        shorts) the decode-side stall and tok/s are measured on the
        decode clock, quiet vs convoy, unified vs disagg, next to the
        roofline report's predicted crossover.

    Uses the same Hkv = 4 smoke-config variant as `run_sharded` so the
    mesh leg can shard KV heads.
    """
    cfg = dataclasses.replace(C.get_smoke_config("qwen25-05b"),
                              num_heads=8, num_kv_heads=4, head_dim=16)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(12)
    prefix = rng.integers(0, cfg.vocab_size,
                          (SHARD_PREFIX_LEN,)).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.integers(0, cfg.vocab_size, (t,)
                                            ).astype(np.int32)])
               for t in (5, 12, 9, 3)]

    eng = GenerationEngine(m, params, **DISAGG_KW)
    rids = [eng.submit(p, DISAGG_NEW, prefix_id="sys") for p in prompts]
    out = eng.drain()
    ref = [[int(t) for t in out[r]] for r in rids]

    legs = [("same", None, None)]
    if jax.device_count() >= 2:
        legs.append(("decode_mesh2", None, serving_mesh(2)))
    identical = True
    handoffs = wire_bytes = 0
    aliased = pages = 0.0
    for tag, pmesh, dmesh in legs:
        ctrl = DisaggController(m, params, handoff_min_tokens=1,
                                prefill_mesh=pmesh, decode_mesh=dmesh,
                                **DISAGG_KW)
        crids = [ctrl.submit(p, DISAGG_NEW, prefix_id="sys")
                 for p in prompts]
        got = ctrl.drain()
        identical &= [[int(t) for t in got[r]] for r in crids] == ref
        st = ctrl.stats()
        handoffs += st.handoffs
        wire_bytes += st.wire_bytes
        aliased += st.aliased_pages
        pages += st.handoff_pages
    identity["disagg_vs_unified"] = identical

    # mixed burst on the decode clock: unified vs disagg, quiet vs convoy
    shorts = [rng.integers(0, cfg.vocab_size,
                           (DISAGG_SHORT,)).astype(np.int32)
              for _ in range(3)]
    longs = [rng.integers(0, cfg.vocab_size,
                          (DISAGG_LONG,)).astype(np.int32)
             for _ in range(2)]
    # unified baseline = one-shot prefill, as in `run_convoy`: the long
    # admission is one monolithic dispatch the decode clock waits out.
    # (The chunked unified engine bounds that stall to a chunk — but at
    # smoke scale every dispatch costs ~the same weight-streaming time,
    # so chunk-vs-decode contrast is invisible on CPU; the structural
    # claim measured here is prefill LEAVING the decode clock entirely.)
    # spec off on both sides: the one-shot path can't speculate, and
    # uniform decode gaps make the stall comparison apples-to-apples
    conv_kw = {k: v for k, v in DISAGG_KW.items()
               if not k.startswith("spec_")}
    uni = GenerationEngine(m, params,
                           **dict(conv_kw, chunked_prefill=False))
    uni.warmup()
    _disagg_warm(uni, longs[0], shorts[0])
    u_conv = _disagg_burst(uni, shorts, 24, longs, 6, decode_clock=False)
    ctrl = DisaggController(m, params, handoff_min_tokens=32, **conv_kw)
    ctrl.warmup()
    _disagg_warm(ctrl, longs[0], shorts[0])
    d_quiet = _disagg_burst(ctrl, shorts, 24, [], 6, decode_clock=True)
    ctrl.reset_stats()
    d_conv = _disagg_burst(ctrl, shorts, 24, longs, 6, decode_clock=True)
    cst = ctrl.stats()
    rep = disagg_report(cfg, decode_batch=DISAGG_KW["num_slots"],
                        context=DISAGG_KW["max_seq"], quant=True)
    tps = {k: r["decode_toks"] / max(r["decode_s"], 1e-9)
           for k, r in (("quiet", d_quiet), ("convoy", d_conv))}
    u_tps = u_conv["decode_toks"] / max(u_conv["decode_s"], 1e-9)

    csv_rows.extend([
        ("serving/disagg_token_identity", str(identical),
         "prefill→handoff→decode ≡ unified "
         f"({'+'.join(t for t, _, _ in legs)})"),
        ("serving/disagg_wire_kib_per_handoff",
         f"{wire_bytes / max(handoffs, 1) / 1024:.1f}",
         "decode-side TTFT is this transfer (int8 codes + scales)"),
        ("serving/disagg_adopt_ms_per_handoff",
         f"{cst.adopt_time_s / max(cst.handoffs, 1) * 1e3:.2f}",
         "wire + scatter into the decode pool, steady state (movers "
         "compiled)"),
        ("serving/disagg_aliased_page_frac",
         f"{aliased / max(pages, 1):.2f}",
         "handoff pages deduped against the decode pool's prefix index"),
        ("serving/disagg_decode_stall_unified_s",
         f"{u_conv['stall']:.3f}",
         "worst short-request token gap, decode clock, convoy burst"),
        ("serving/disagg_decode_stall_disagg_s",
         f"{d_conv['stall']:.3f}",
         "long prefill lives on the other engine"),
        ("serving/disagg_decode_tps_quiet", f"{tps['quiet']:.1f}",
         "short-request decode-side tok/s, no long prefill in flight"),
        ("serving/disagg_decode_tps_convoy", f"{tps['convoy']:.1f}",
         f"same burst + {DISAGG_LONG}-token prefill convoy "
         f"(unified: {u_tps:.1f})"),
        ("serving/disagg_predicted_crossover_tokens",
         str(rep["crossover_prompt_tokens"]),
         f"roofline: prefill {rep['prefill_bound']}-bound at "
         f"{rep['prefill_intensity']:.0f} F/B, decode "
         f"{rep['decode_bound']}-bound at "
         f"{rep['decode_intensity']:.0f} F/B"),
    ])
    return {"identical": identical, "handoffs": handoffs,
            "wire_bytes": wire_bytes,
            "convoy_handoffs": cst.handoffs, "direct": cst.direct,
            "stall": {"unified": u_conv["stall"],
                      "disagg": d_conv["stall"]},
            "decode_tps": {"quiet": tps["quiet"], "convoy": tps["convoy"],
                           "unified_convoy": u_tps},
            "crossover_pred": rep["crossover_prompt_tokens"]}


# ---------------------------------------------------------------------------
# Compression × speed: the AWQ W4 weight stream through the serving grid
# ---------------------------------------------------------------------------

AWQ_FEATURES = {
    "plain": {},
    "int8": {"kv_quant": "int8"},
    "prefix": {},                       # prefix_id at submit time
    "spec": {"spec_decode": "ngram", "spec_k": SPEC_K},
}
AWQ_SMOKE_FEATURES = ("plain", "spec")


def run_awq(m, params, csv_rows, identity, smoke=False):
    """Float vs AWQ-W4 params through the serving feature grid.

    Three parts, all through the PUBLIC engine API:

      * **identity battery** — the quantized engine streams greedy tokens
        under the Pallas kernel (interpret mode) and under the pure-jnp
        ``ref`` oracle through the FULL feature stack (chunked + int8 KV +
        prefix sharing + ngram spec); the comparison registers as a gated
        identity section.
      * **weight-stream accounting** — ``stats().weight_bytes_per_token``
        for float vs packed params: the bytes one decode step streams per
        emitted token, the quantity the paper's INT4 compression targets
        (reported next to the KV bytes/token column `run_kv_quant` owns).
      * **ms-per-token grid** — float vs AWQ × feature cells, separate
        prefill and decode probes (untimed compile pass first, engine
        reused so only the probes are timed). Off-TPU the AWQ cells run
        the jnp dequant oracle — the grid is then a correctness-shaped
        cost model; the kernel regime needs a TPU backend.
    """
    import jax.numpy as jnp

    import repro.core.qlinear as Q
    from repro.core import quantize_params
    cfg = m.cfg
    qp, report = quantize_params(params)
    assert report.quantized, "config has no quantizable linears"

    # --- identity battery: Pallas kernel vs jnp oracle, full stack -------
    rng = np.random.default_rng(19)
    id_prefix = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
    id_prompts = [np.concatenate([id_prefix,
                                  rng.integers(0, cfg.vocab_size, (t,)
                                               ).astype(np.int32)])
                  for t in (5, 12, 9, 3)]

    def _streams(impl):
        Q.set_execution_config(impl=impl, compute_dtype=jnp.float32)
        eng = _fresh_engine(m, qp, max_seq=64, num_slots=4, page_size=8,
                            prefill_chunk=4, kv_quant="int8",
                            spec_decode="ngram", spec_k=SPEC_K)
        rids = [eng.submit(p, 10, prefix_id="sys") for p in id_prompts]
        out = eng.drain()
        return [list(out[r]) for r in rids]

    prev = Q.get_execution_config()
    try:
        identical = _streams("ref") == _streams("kernel_interpret")
    finally:
        Q._EXEC = prev
    identity["awq_kernel_vs_ref"] = identical
    csv_rows.append(("serving/awq_token_identity", str(identical),
                     "AWQ kernel ≡ jnp ref through chunked+int8+prefix+spec"))

    # --- weight stream accounting ----------------------------------------
    wb = {}
    for tag, pp in (("float", params), ("awq", qp)):
        st = _fresh_engine(m, pp).stats()
        wb[tag] = st.weight_bytes
        csv_rows.append(
            (f"serving/weight_bytes_per_token_{tag}",
             f"{st.weight_bytes_per_token:.0f}",
             "weight bytes streamed per decoded token "
             "(whole model per step until spec amortizes it)"))
    csv_rows.append(
        ("serving/awq_weight_bytes_reduction",
         f"{1 - wb['awq'] / wb['float']:.1%}",
         f"{wb['float']} -> {wb['awq']} model bytes"))

    # --- compression × speed grid -----------------------------------------
    feats = AWQ_SMOKE_FEATURES if smoke else tuple(AWQ_FEATURES)
    prefill_len = 24 if smoke else 64
    decode_new = 8 if smoke else 24
    n_req = 2 if smoke else 4
    grid = {}
    for ptag, pp in (("float", params), ("awq", qp)):
        for feat in feats:
            rng = np.random.default_rng(23)
            if feat == "spec":
                pat = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
                short = [np.tile(pat, 2) for _ in range(n_req)]
            else:
                short = [rng.integers(0, cfg.vocab_size, (8,)
                                      ).astype(np.int32)
                         for _ in range(n_req)]
            pref = rng.integers(0, cfg.vocab_size, (prefill_len - 8,)
                                ).astype(np.int32)
            long_ = [np.concatenate([pref,
                                     rng.integers(0, cfg.vocab_size, (8,)
                                                  ).astype(np.int32)])
                     for _ in range(n_req)]
            pid = "sys" if feat == "prefix" else None
            eng = _fresh_engine(m, pp, **AWQ_FEATURES[feat])

            def _drain(prompts, new, prefix_id=None):
                for p in prompts:
                    eng.submit(p, new, prefix_id=prefix_id)
                t0 = time.perf_counter()
                eng.drain()
                return time.perf_counter() - t0

            _drain(long_, 1, pid)               # untimed: compiles, and for
            _drain(short, decode_new)           # "prefix" registers the pages
            pre_ms = _drain(long_, 1, pid) * 1e3 / (n_req * prefill_len)
            dec_ms = _drain(short, decode_new) * 1e3 / (n_req * decode_new)
            grid[f"{ptag}/{feat}"] = {"prefill_ms_per_tok": pre_ms,
                                      "decode_ms_per_tok": dec_ms}
            csv_rows.extend([
                (f"serving/awq_grid_{ptag}_{feat}_prefill_ms_per_tok",
                 f"{pre_ms:.2f}",
                 f"{n_req} reqs x {prefill_len}-token prompts"),
                (f"serving/awq_grid_{ptag}_{feat}_decode_ms_per_tok",
                 f"{dec_ms:.2f}",
                 f"{n_req} reqs x {decode_new} new tokens"),
            ])
    return {"identical": identical, "weight_bytes": wb, "grid": grid}


# ---------------------------------------------------------------------------
# Tiered SLO: priority preemption + KV page spill under overload
# ---------------------------------------------------------------------------

SLO_HOLD_STEPS = 4          # dispatches the low tier runs alone before the
                            # interactive tier arrives (mid-decode overload)
SLO_STEP_CAP = 5000         # drain-loop fuse: a wedged scheduler raises in
                            # `run()`, this bounds a hypothetical step leak


def _serve_tiered(eng, lo_reqs, hi_reqs, hold_steps=SLO_HOLD_STEPS):
    """Submit the low tier, let it hold the engine for ``hold_steps``
    dispatches, then submit the interactive tier and step to drain.

    Interactive TTFT is counted in *dispatch steps since submission*:
    greedy decode makes step counts a pure function of the schedule, so
    the gate can assert "preemption held TTFT down" deterministically —
    the wall-clock numbers are reported alongside for scale.
    Returns (streams, ttft_steps, ttft_wall, stats).
    """
    lo = [eng.submit(p, mn, priority=0) for p, mn in lo_reqs]
    for _ in range(hold_steps):
        eng.step()
    hi = [eng.submit(p, mn, priority=1) for p, mn in hi_reqs]
    hi_pending = set(hi)
    first_step, first_wall = {}, {}
    streams: dict[int, list] = {}
    step = 0
    t0 = time.perf_counter()
    while not eng.idle:
        events = eng.step()
        step += 1
        assert step <= SLO_STEP_CAP, "tiered-SLO drain did not converge"
        now = time.perf_counter() - t0
        for rid, _tok in events:
            if rid in hi_pending:
                hi_pending.discard(rid)
                first_step[rid] = step
                first_wall[rid] = now
        for rid, toks in eng.collect().items():
            streams[rid] = [int(t) for t in toks]
    return ({r: streams[r] for r in lo + hi},
            [first_step[r] for r in hi],
            [first_wall[r] for r in hi], eng.stats())


def _matches_generate(eng, streams, reqs_by_rid):
    """Every served stream ≡ an uninterrupted per-request `generate()`."""
    import jax.numpy as jnp
    for rid, (p, mn) in reqs_by_rid.items():
        ref = np.asarray(
            eng.generate({"tokens": jnp.asarray(p)[None, :]}, mn)[0])
        if streams[rid] != [int(t) for t in ref[: len(streams[rid])]]:
            return False
    return True


def run_slo(m, params, csv_rows, identity, smoke=False):
    """Tiered-SLO overload: priority preemption + KV page spill.

    Two overload shapes, each served with and without the new machinery,
    TTFT compared in deterministic dispatch steps:

      * **slot contention** — every slot of a 2-slot engine is held by
        low-priority batch decodes when two interactive requests arrive.
        Baseline: they convoy behind a full batch budget. Preemption:
        the scheduler spills a victim's committed KV pages to the host
        tier, serves the interactive tier, then restores the victim at
        its commit watermark (zero prefill recompute).
      * **long-context reservation** — one long-budget request's
        worst-case page reservation starves every short request under
        conservative admission (the 32k-convoy problem at smoke scale).
        Optimistic admission books only what is committed, admits the
        shorts immediately, and relieves later pool pressure by
        spilling the long request.

    All preempted streams are asserted token-identical to uninterrupted
    per-request ``generate()`` references — the "spill/restore changed
    no bytes" identity section the gate requires.
    """
    cfg = m.cfg
    rng = np.random.default_rng(31)

    def _reqs(n, plen, mn):
        return [(rng.integers(0, cfg.vocab_size, (plen,)).astype(np.int32),
                 mn) for _ in range(n)]

    res: dict = {}
    token_identity = True

    # --- scenario 1: slot contention ------------------------------------
    lo_budget = 32 if smoke else 64
    lo_reqs = _reqs(2, 12, lo_budget)
    hi_reqs = _reqs(2, 6, 4)
    contention = {}
    for tag, kw in (("preempt", {"preemption": True}), ("base", {})):
        eng = _fresh_engine(m, params, num_slots=2, **kw)
        eng.warmup()
        streams, tsteps, twall, st = _serve_tiered(eng, lo_reqs, hi_reqs)
        if tag == "preempt":
            rids = list(streams)
            reqs = dict(zip(rids, lo_reqs + hi_reqs))
            token_identity &= _matches_generate(eng, streams, reqs)
        contention[tag] = {
            "ttft_steps_p95": float(np.percentile(tsteps, 95)),
            "ttft_wall_p95": float(np.percentile(twall, 95)),
            "preemptions": st.preemptions, "restores": st.restores,
            "spilled_pages": st.spilled_pages,
            "restore_ms_mean": st.restore_ms_mean,
        }
    res["contention"] = contention

    # --- scenario 2: long-context reservation convoy --------------------
    # the long request's worst-case reservation ≈ the whole pool; sized so
    # conservative admission blocks every short until the long finishes
    if smoke:
        long_plen, long_mn, max_seq = 12, 61, MAX_SEQ
        n_short, short_mn = 3, 16
    else:
        long_plen, long_mn, max_seq = 64, 256, 384
        # exactly the free slots (more would slot-preempt the long and
        # park it before the pool ever dries), with budgets long enough
        # to still be decoding when the long's growing footprint crosses
        # the pool (~step 66 of their 90): pressure must relieve by
        # spilling the long, not by it finishing first
        n_short, short_mn = NUM_SLOTS - 1, 90
    long_pages = -(-(long_plen + long_mn - 1) // PAGE_SIZE)
    num_pages = long_pages + 2           # +1 scratch, +1 free: shorts need
    long_req = _reqs(1, long_plen, long_mn)     # 2+ pages -> blocked
    short_reqs = _reqs(n_short, 6, short_mn)
    longctx = {}
    for tag, kw in (("optimistic", {"preemption": True,
                                    "admission": "optimistic"}),
                    ("reserved", {})):
        eng = _fresh_engine(m, params, max_seq=max_seq, num_pages=num_pages,
                            **kw)
        eng.warmup()
        streams, tsteps, twall, st = _serve_tiered(eng, long_req, short_reqs)
        if tag == "optimistic":
            rids = list(streams)
            reqs = dict(zip(rids, long_req + short_reqs))
            token_identity &= _matches_generate(eng, streams, reqs)
        longctx[tag] = {
            "ttft_steps_p95": float(np.percentile(tsteps, 95)),
            "ttft_wall_p95": float(np.percentile(twall, 95)),
            "pressure_spills": st.pressure_spills,
            "preemptions": st.preemptions, "restores": st.restores,
        }
    res["longctx"] = longctx
    res["token_identity"] = token_identity
    identity["preempt_vs_uninterrupted"] = token_identity

    pre, base = contention["preempt"], contention["base"]
    opt, rsv = longctx["optimistic"], longctx["reserved"]
    csv_rows.extend([
        ("serving/slo_interactive_ttft_steps_p95_preempt",
         f"{pre['ttft_steps_p95']:.0f}",
         "dispatch steps from arrival to first token, 2 slots fully held "
         "by low-priority decodes"),
        ("serving/slo_interactive_ttft_steps_p95_base",
         f"{base['ttft_steps_p95']:.0f}",
         "no preemption: convoys behind the whole batch budget"),
        ("serving/slo_interactive_ttft_wall_p95_preempt_s",
         f"{pre['ttft_wall_p95']:.3f}", ""),
        ("serving/slo_interactive_ttft_wall_p95_base_s",
         f"{base['ttft_wall_p95']:.3f}", ""),
        ("serving/slo_preemptions", str(pre["preemptions"]),
         f"{pre['spilled_pages']} page strips spilled to the host tier"),
        ("serving/slo_restores", str(pre["restores"]),
         f"{pre['restore_ms_mean']:.2f} ms mean restore latency, resumed "
         f"at the commit watermark (zero recompute)"),
        ("serving/slo_longctx_ttft_steps_p95_optimistic",
         f"{opt['ttft_steps_p95']:.0f}",
         f"{long_plen}+{long_mn}-token request in a {num_pages}-page pool"),
        ("serving/slo_longctx_ttft_steps_p95_reserved",
         f"{rsv['ttft_steps_p95']:.0f}",
         "worst-case reservation starves the shorts until the long ends"),
        ("serving/slo_longctx_pressure_spills",
         str(opt["pressure_spills"]),
         "optimistic over-admission relieved by spilling the long request"),
        ("serving/slo_token_identity", str(token_identity),
         "preempted/spilled streams ≡ uninterrupted generate()"),
    ])
    return res


# ---------------------------------------------------------------------------
# Multi-replica fleet: prefix-affinity router
# ---------------------------------------------------------------------------

def make_cluster_workload(cfg, n_clusters=2, num_requests=8, prefix_len=32,
                          new_tokens=8, rate=ARRIVAL_RATE, seed=11):
    """Clustered-prefix Poisson burst: request ``i`` belongs to cluster
    ``i % n_clusters`` and shares that cluster's page-aligned system
    prefix. Returns (prefixes, [(arrival, prompt, max_new, prefix_id)])."""
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(0, cfg.vocab_size, (prefix_len,)
                             ).astype(np.int32) for _ in range(n_clusters)]
    arrivals = np.cumsum(rng.exponential(1.0 / rate, num_requests))
    reqs = []
    for i in range(num_requests):
        c = i % n_clusters
        tail = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
        reqs.append((float(arrivals[i]),
                     np.concatenate([prefixes[c], tail]),
                     new_tokens, f"cluster{c}"))
    return prefixes, reqs


def _warm_fleet(fleet, prefixes):
    """Pin every cluster prefix (sticky), run one request per cluster
    through the fleet so the pages are resident, and zero the stats so
    the timed burst reports only itself."""
    for c, pfx in enumerate(prefixes):
        fleet.pin_prefix(f"cluster{c}")
        fleet.submit(np.concatenate(
            [pfx, np.full((4,), c + 1, np.int32)]), 2,
            prefix_id=f"cluster{c}")
    fleet.drain()
    fleet.reset_stats()


def _run_fleet(router, workload):
    """Replay a clustered workload through a Router; same contract as
    `run_continuous` but fleet-wide (skipped = sum over replicas)."""
    pending = sorted(enumerate(workload), key=lambda r: r[1][0])
    arrival_of, first, finish = {}, {}, {}
    t0 = time.perf_counter()
    i = 0
    while True:
        now = time.perf_counter() - t0
        while i < len(pending) and pending[i][1][0] <= now:
            _, (arrival, prompt, mn, pid) = pending[i]
            rid = router.submit(prompt, mn, prefix_id=pid)
            arrival_of[rid] = arrival
            i += 1
        events = router.step()
        now = time.perf_counter() - t0
        for rid, _tok in events:
            if rid in arrival_of and rid not in first:
                first[rid] = now
        for rid in router.collect():
            finish[rid] = now
        if len(finish) == len(workload):
            break
        if i < len(pending) and router.idle:
            time.sleep(0.0005)
    dt = time.perf_counter() - t0
    useful = sum(mn for _, _, mn, _ in workload)
    skipped = sum(getattr(s, "prefill_tokens_skipped", 0)
                  for s in router.stats())
    return {"useful": useful, "dt": dt, "tps": useful / dt,
            "ttft_p95": float(np.percentile(
                [first[r] - arrival_of[r] for r in first], 95)),
            "skipped": int(skipped)}


def run_router(m, params, csv_rows, identity, smoke=False):
    """Multi-replica serving fleet through the prefix-affinity `Router`.

    Four measurements:

      * **router_vs_single** (gated identity) — the same burst through a
        bare engine and a 1-replica fleet must produce byte-identical
        greedy streams: the router adds placement, never changes tokens.
      * **affinity vs. random** — two warmed 2-replica fleets serve the
        clustered burst; affinity placement routes each cluster to the
        replica already holding its prefix pages and must skip strictly
        more prefill tokens than seeded-random placement (tok/s asserted
        at full scale, where the skipped work dominates wall clock).
      * **throughput vs. replica count** — the same warmed burst through
        fleets of 1/2(/4 at full scale); informational on one host
        (replicas share the device), the scaling story is the row.
      * **drain under load** — submit 2x the burst to the 2-replica
        fleet, step a few times, `drain_replica(0)` mid-flight, then
        drain the fleet: every stream must come back exactly once and
        byte-equal to its bare-engine reference (zero loss, zero
        duplication), with rerouted-request count and drain-phase TTFT
        reported.
    """
    cfg = m.cfg
    n_req = 8 if smoke else NUM_REQUESTS
    mn = 8 if smoke else 16
    # full scale doubles the shared prefix (8 pages): the skipped
    # prefill has to dominate wall-clock noise for the tok/s assert
    prefixes, workload = make_cluster_workload(
        cfg, num_requests=n_req, new_tokens=mn,
        prefix_len=32 if smoke else 64)
    res: dict = {"topology": {}}

    # --- 1-replica fleet ≡ bare engine (gated identity) ---------------
    eng_ref = _fresh_engine(m, params)
    eng_ref.warmup()
    rids = [eng_ref.submit(p, mn_, prefix_id=pid)
            for _, p, mn_, pid in workload]
    refs = eng_ref.drain()
    ref_streams = [list(refs[r]) for r in rids]
    fleet1 = Router([_fresh_engine(m, params)])
    fleet1.warmup()
    grids = [fleet1.submit(p, mn_, prefix_id=pid)
             for _, p, mn_, pid in workload]
    fout = fleet1.drain()
    identical = [list(fout[g]) for g in grids] == ref_streams
    identity["router_vs_single"] = identical
    res["identical"] = identical

    # --- affinity vs random placement (both fleets warmed + pinned) ---
    fleets = {}
    for policy in ("affinity", "random"):
        fleet = Router([_fresh_engine(m, params) for _ in range(2)],
                       placement=policy, seed=7)
        fleet.warmup()
        _warm_fleet(fleet, prefixes)
        r = _run_fleet(fleet, workload)
        r["affinity_hits"] = fleet.router_stats.affinity_hits
        res[policy] = r
        fleets[policy] = fleet

    # --- throughput vs replica count ----------------------------------
    # the 2-replica number is the affinity fleet's run above; 1 (and 4,
    # at full scale) get their own warmed fleets so every size pays the
    # same pre-warm
    scale = {2: res["affinity"]["tps"]}
    sizes = (1,) if smoke else (1, 4)
    for n in sizes:
        fleet = Router([_fresh_engine(m, params) for _ in range(n)])
        fleet.warmup()
        _warm_fleet(fleet, prefixes)
        scale[n] = _run_fleet(fleet, workload)["tps"]
    res["scale_tps"] = {str(k): v for k, v in sorted(scale.items())}
    res["topology"] = {
        "fleet_sizes": sorted(scale), "mesh_axis": 1,
        "devices": jax.device_count(),
    }

    # --- elastic drain under load: zero loss, zero duplication --------
    fleet = fleets["affinity"]
    both = workload + [(a, p, mn_, pid) for a, p, mn_, pid in workload]
    drids = [fleet.submit(p, mn_, prefix_id=pid) for _, p, mn_, pid in both]
    t0 = time.perf_counter()
    first: dict[int, float] = {}
    for _ in range(3):                  # work is genuinely in flight
        for rid, _tok in fleet.step():
            first.setdefault(rid, time.perf_counter() - t0)
    for rid, _tok in fleet.drain_replica(0):
        first.setdefault(rid, time.perf_counter() - t0)
    dout = fleet.drain()
    streams = [list(dout[r]) for r in drids if r in dout]
    want = ref_streams + ref_streams    # greedy ⇒ placement-independent
    res["drain"] = {
        "lost": len(drids) - len(streams),
        "duplicated": len(dout) - len(set(dout)),
        "identical": streams == want,
        "reroutes": fleet.router_stats.reroutes,
        "drain_ttft_p95": float(np.percentile(
            [first[r] for r in first], 95)) if first else 0.0,
    }

    aff, rnd = res["affinity"], res["random"]
    csv_rows.extend([
        ("serving/router_affinity_tps", f"{aff['tps']:.1f}",
         f"2 replicas, {n_req}-request clustered burst, "
         f"{aff['affinity_hits']} affinity hits"),
        ("serving/router_random_tps", f"{rnd['tps']:.1f}",
         "same burst, seeded-random placement"),
        ("serving/router_affinity_prefill_skipped", str(aff["skipped"]),
         "prompt tokens never recomputed (placed onto warm pages)"),
        ("serving/router_random_prefill_skipped", str(rnd["skipped"]),
         "random placement misses the warm replica about half the time"),
        ("serving/router_scale_tps",
         " ".join(f"{k}x:{v:.1f}" for k, v in sorted(res["scale_tps"]
                                                     .items())),
         "sustained tok/s vs replica count (one host: informational)"),
        ("serving/router_identity", str(identical),
         "1-replica fleet ≡ bare engine (greedy streams)"),
        ("serving/router_drain_reroutes", str(res["drain"]["reroutes"]),
         "queued requests moved off the draining replica, rids kept"),
        ("serving/router_drain_ttft_p95_s",
         f"{res['drain']['drain_ttft_p95']:.3f}",
         "TTFT across the drain-under-load burst"),
        ("serving/router_drain_zero_loss",
         str(res["drain"]["lost"] == 0 and res["drain"]["identical"]),
         "every stream delivered exactly once, byte-equal to references"),
    ])
    return res


def run(csv_rows: list, smoke: bool = False) -> dict:
    cfg = C.get_smoke_config("qwen25-05b")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    identity: dict = {}   # section name → asserted comparison outcome

    if smoke:
        # tier-1 end-to-end gate: small burst through the chunked engine,
        # identity vs one-shot + generate(), prefix-FLOP accounting, one
        # speculative-decode burst, one sharded burst
        workload = make_workload(cfg, num_requests=6,
                                 budgets=(24, 6, 8, 6, 12, 8))
        identical = verify_token_identity(m, params, workload[:3], identity)
        eng_cont = _fresh_engine(m, params)
        r = run_continuous(eng_cont, workload)
        pack = _padding_rows(eng_cont.stats(), csv_rows,
                             tag="serving/smoke_padding")
        kv = run_kv_quant(m, params, csv_rows)
        prefix = run_prefix_sharing(m, params, csv_rows, prefix_len=32,
                                    num_requests=3, new_tokens=8)
        spec = run_spec(m, params, csv_rows, identity, num_requests=4,
                        new_tokens=12, tag_prefix="serving/smoke_spec")
        tree = run_tree_spec(m, params, csv_rows, identity, num_requests=4,
                             new_tokens=12, tag_prefix="serving/smoke_tree")
        par = run_parallel(m, params, csv_rows, identity, new_tokens=8,
                           tag_prefix="serving/smoke_parallel")
        sharded = run_sharded(csv_rows, identity)
        disagg = run_disagg(csv_rows, identity, smoke=True)
        awq = run_awq(m, params, csv_rows, identity, smoke=True)
        slo = run_slo(m, params, csv_rows, identity, smoke=True)
        router = run_router(m, params, csv_rows, identity, smoke=True)
        csv_rows.extend([
            ("serving/smoke_sustained_tps", f"{r['useful'] / r['dt']:.1f}",
             f"{r['useful']} tokens, {r['steps']} unified dispatches"),
            ("serving/smoke_ttft_p95_s",
             f"{np.percentile(r['ttfts'], 95):.3f}", ""),
            ("serving/smoke_token_identity", str(identical),
             "chunked ≡ one-shot ≡ generate()"),
        ])
        return {"token_identical": identical, "spec": spec, "tree": tree,
                "parallel": par, "padding": pack, "sharded": sharded,
                "disagg": disagg, "awq": awq, "slo": slo, "router": router,
                "identity_sections": identity, **kv, **prefix}

    workload = make_workload(cfg)
    su, sl, ss, sdt = run_static(_fresh_engine(m, params), workload)
    eng_cont = _fresh_engine(m, params)
    r = run_continuous(eng_cont, workload)
    cu, cl, ct, cs, cdt = (r["useful"], r["latencies"], r["ttfts"],
                           r["steps"], r["dt"])
    pack = _padding_rows(eng_cont.stats(), csv_rows)
    identical = verify_token_identity(m, params, workload, identity)
    convoy = run_convoy(m, params, csv_rows)
    kv = run_kv_quant(m, params, csv_rows)
    prefix = run_prefix_sharing(m, params, csv_rows)
    spec = run_spec(m, params, csv_rows, identity)
    tree = run_tree_spec(m, params, csv_rows, identity)
    par = run_parallel(m, params, csv_rows, identity)
    sharded = run_sharded(csv_rows, identity)
    disagg = run_disagg(csv_rows, identity)
    awq = run_awq(m, params, csv_rows, identity)
    slo = run_slo(m, params, csv_rows, identity)
    router = run_router(m, params, csv_rows, identity)

    s_tps, c_tps = su / sdt, cu / cdt
    rows = [
        ("serving/static_sustained_tps", f"{s_tps:.1f}",
         f"{su} tokens, {ss} decode steps"),
        ("serving/continuous_sustained_tps", f"{c_tps:.1f}",
         f"{cu} tokens, {cs} unified dispatches"),
        ("serving/continuous_speedup", f"{c_tps / s_tps:.2f}x",
         "sustained tok/s vs static batch"),
        ("serving/static_p50_latency_s", f"{np.percentile(sl, 50):.3f}", ""),
        ("serving/static_p95_latency_s", f"{np.percentile(sl, 95):.3f}", ""),
        ("serving/continuous_p50_latency_s",
         f"{np.percentile(cl, 50):.3f}", ""),
        ("serving/continuous_p95_latency_s",
         f"{np.percentile(cl, 95):.3f}", ""),
        ("serving/continuous_ttft_p50_s", f"{np.percentile(ct, 50):.3f}", ""),
        ("serving/continuous_ttft_p95_s", f"{np.percentile(ct, 95):.3f}", ""),
        ("serving/greedy_token_identity", str(identical),
         "chunked ≡ one-shot ≡ sequential generate()"),
    ]
    csv_rows.extend(rows)
    return {"static_tps": s_tps, "continuous_tps": c_tps,
            "speedup": c_tps / s_tps,
            "static_p95": float(np.percentile(sl, 95)),
            "continuous_p95": float(np.percentile(cl, 95)),
            "ttft_p95": float(np.percentile(ct, 95)),
            "token_identical": identical, "spec": spec, "tree": tree,
            "parallel": par, "padding": pack,
            "sharded": sharded, "disagg": disagg, "awq": awq, "slo": slo,
            "router": router,
            "identity_sections": identity, **convoy, **kv, **prefix}


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced run for the tier-1 gate")
    ap.add_argument("--history-file", default=None,
                    help="tracked run-history JSON (default: repo-root "
                         "BENCH_serving.json); every run appends a record")
    args = ap.parse_args()
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows: list = []
    out = run(rows, smoke=args.smoke)
    for r in rows:
        print(",".join(str(x) for x in r))
    # tracked history: append a schema'd record BEFORE any gate can exit,
    # so failed runs leave evidence too (run_tier1 gates on this file)
    hist_path = pathlib.Path(args.history_file) if args.history_file else \
        pathlib.Path(__file__).resolve().parent.parent / "BENCH_serving.json"
    # provenance stamp: which code produced this record (the trajectory
    # gate compares adjacent records — a regression should name a commit)
    try:
        git_commit = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=pathlib.Path(__file__).resolve().parent,
            timeout=10).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        git_commit = "unknown"
    record = {
        "schema": 1,
        "timestamp": time.time(),
        "smoke": bool(args.smoke),
        "git_commit": git_commit,
        "jax_version": jax.__version__,
        "jax_devices": jax.device_count(),
        "metrics": {name: value for name, value, _ in rows},
        "identity_sections": out.get("identity_sections", {}),
        "awq": {"weight_bytes": out["awq"]["weight_bytes"],
                "grid": out["awq"]["grid"]},
        "replica_topology": out["router"]["topology"],
    }
    try:
        history = json.loads(hist_path.read_text())
    except (FileNotFoundError, json.JSONDecodeError):
        history = []
    if not isinstance(history, list):
        history = []
    history.append(record)
    hist_path.write_text(json.dumps(history, indent=1) + "\n")
    print(f"HISTORY appended to {hist_path} ({len(history)} records)")
    # the skip guard: every asserted identity section must have RUN and
    # passed — a section that was silently skipped leaves its key missing,
    # which fails the gate just like a mismatch would
    sections = out.get("identity_sections", {})
    bad = [k for k in REQUIRED_IDENTITY if sections.get(k) is not True]
    if bad:
        print(f"IDENTITY-SECTIONS missing or failed: {bad} "
              f"(ran: {sections})", file=sys.stderr)
        sys.exit(1)
    print(f"IDENTITY-SECTIONS ok: {sorted(sections)}")
    assert out["token_identical"]
    assert out["kv_bytes_reduction"] >= 0.40
    # sharded pools must actually stripe: with >1 device the per-device
    # bytes at the largest axis shrink by the axis size (exactly linear —
    # Hkv divides), and streams matched (asserted via identity sections)
    sh = out["sharded"]
    if len(sh["sizes"]) > 1:
        lo, hi = sh["sizes"][0], sh["sizes"][-1]
        # global footprint is axis-invariant …
        assert sh["bytes_per_device"][hi] * hi \
            == sh["bytes_per_device"][lo] * lo
        # … so per-device bytes shrink linearly with the axis size
        assert sh["bytes_per_device"][hi] \
            == sh["bytes_per_device"][lo] * lo // hi
    # prefix-aware chunked prefill must actually skip the aliased pages
    assert out["prefix_chunked"]["skipped"] > 0
    assert out["prefix_chunked"]["prefill_tokens"] \
        < out["prefix_unshared"]["prefill_tokens"]
    # speculative decoding: greedy streams never change, the drafter
    # actually fires, and accounting stays sane
    assert out["spec"]["identical"]
    assert out["spec"]["spec"]["drafted"] > 0
    assert 0 <= out["spec"]["spec"]["accepted"] \
        <= out["spec"]["spec"]["drafted"]
    # tree speculation: the ngram tree drafter actually fired, and on the
    # branchy burst the depth-1 hedge beats linear speculation outright —
    # the same streams in strictly fewer weight passes
    tr = out["tree"]
    assert tr["identical"]
    assert tr["tree"]["drafted"] > 0
    assert 0 <= tr["tree"]["accepted"] <= tr["tree"]["drafted"]
    assert tr["branchy"]["tree"]["steps"] < tr["branchy"]["linear"]["steps"]
    assert tr["branchy"]["tree"]["tokens_per_step"] \
        > tr["branchy"]["linear"]["tokens_per_step"]
    # parallel sampling: siblings alias prompt pages and skip aliased
    # prefill; n separate submissions alias nothing
    par = out["parallel"]
    assert par["identical"]
    assert par["shared_pages"] > 0 and par["sep_shared"] == 0
    assert par["skipped"] > 0
    # the packed weight stream must actually be smaller than the float one
    assert out["awq"]["weight_bytes"]["awq"] \
        < out["awq"]["weight_bytes"]["float"]
    # tiered SLO (deterministic step-count TTFT, so smoke can assert it):
    # preemption actually fired, restores balanced, and the interactive
    # tier's p95 TTFT beat the no-preemption convoy — same for optimistic
    # admission vs the worst-case-reservation baseline
    slo = out["slo"]
    assert slo["contention"]["preempt"]["preemptions"] >= 1
    assert slo["contention"]["preempt"]["restores"] \
        == slo["contention"]["preempt"]["preemptions"]
    assert slo["contention"]["preempt"]["spilled_pages"] > 0
    assert slo["contention"]["preempt"]["ttft_steps_p95"] \
        < slo["contention"]["base"]["ttft_steps_p95"]
    assert slo["longctx"]["optimistic"]["ttft_steps_p95"] \
        < slo["longctx"]["reserved"]["ttft_steps_p95"]
    assert slo["longctx"]["optimistic"]["pressure_spills"] >= 1
    assert slo["token_identity"]
    # disaggregation: the handoff path actually carried pages (identity
    # is gated via REQUIRED_IDENTITY), routing split the convoy burst,
    # and the decode side saw bytes on the wire
    dg = out["disagg"]
    assert dg["handoffs"] >= 1 and dg["wire_bytes"] > 0
    assert dg["convoy_handoffs"] >= 1 and dg["direct"] >= 1
    # fleet routing: affinity placement must land clustered requests on
    # their warm replica — strictly more prefill tokens skipped than the
    # seeded-random fleet on the same burst — and the mid-flight
    # drain_replica must deliver every stream exactly once, byte-equal
    # to bare-engine references (zero loss, zero duplication)
    rt = out["router"]
    assert rt["affinity"]["skipped"] > rt["random"]["skipped"]
    assert rt["drain"]["lost"] == 0 and rt["drain"]["duplicated"] == 0
    assert rt["drain"]["identical"]
    if not args.smoke:
        # the headline claims: sharing saves FLOPs (not just memory),
        # TTFT p95 beats the one-shot baseline on the shared-prefix
        # burst, chunking bounds the convoy-effect decode stall, and on
        # the repetitive burst one weight pass emits > 1 token on average
        assert out["prefix_flops_saved"] > 0.5
        assert out["prefix_chunked"]["ttft_p95"] \
            < out["prefix_oneshot"]["ttft_p95"]
        assert out["convoy"]["chunked"]["short_stall_max"] \
            < out["convoy"]["oneshot"]["short_stall_max"]
        assert out["spec"]["spec"]["tokens_per_step"] > 1.0
        assert out["spec"]["spec"]["steps"] < out["spec"]["plain"]["steps"]
        # disaggregation's headline: with the long prefill exiled to the
        # other engine, the decode side's worst short-request stall drops
        # (measured on the decode clock — wall time can't see it on one
        # host). Smoke reports the same rows without asserting, like the
        # convoy section.
        assert out["disagg"]["stall"]["disagg"] \
            < out["disagg"]["stall"]["unified"]
        # routing's headline: the skipped prefill work shows up as
        # sustained throughput at full scale (smoke bursts are too
        # short for the wall clock to resolve it reliably on CPU)
        assert rt["affinity"]["tps"] > rt["random"]["tps"]
