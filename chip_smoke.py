"""Smoke test of the serving path on TPU: Qwen2.5-0.5B at its published
widths, AWQ-W4 (GS 64) weights, int8 paged KV, continuous batching.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # 2 replicas x TP-2 behind a Router

One process drives every chip it uses; it starts no child. Weights are
random from ``--seed``, calibrated and packed by the launcher's own AWQ
pipeline (`repro.launch.serve.awq_quantize`), and requests go through the
public engine API (``submit``/``step``/``drain``). The one-chip run checks
that

  * the compiled serving step holds both Pallas kernels (AWQ matmul and
    paged attention) as ``tpu_custom_call``s, at decode and chunk widths;
  * AWQ ``impl="kernel"`` and ``impl="ref"`` agree on every packed
    linear and in the logits of a full-width prefill (`KERNEL_REL_TOL`);
  * every greedy stream is tie-tolerantly right: a cache-free forward over
    prompt + output puts each chosen token within `TIE_MARGIN` of the top
    logit at its position, and a random-token stream fails that check.

``--four-chips`` runs only the fleet phase: the same requests on two TP-2
replicas, which must sit on disjoint chip pairs covering all four chips;
their streams pass the same check against the unsharded model on one
chip, and are compared with a one-chip engine's. Timings printed are
smoke readings, not benchmark results. Any failure exits non-zero; the
last line of stdout is ``{"ok": true, "device": {...}}`` only on success.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

# A served token must score within this many standard deviations (of the
# position's logits over the vocabulary) of the top logit of a cache-free
# bf16 forward over the same context. The served step differs from that
# forward by int8 KV codes (absmax error <= 1/254 of each (position, head)
# row) and by bf16 rounding in another order, which moves logits by a
# small fraction of a standard deviation; near-ties (top-2 gaps of ~1e-3
# are common among 151,936 random logits) may then flip, and the margin
# admits that. A random token sits ~4.9 deviations below the top, so a
# random stream fails at nearly every position.
TIE_MARGIN = 0.25
# ||kernel - ref|| / ||ref|| of every packed linear at decode (M 8) and
# prefill (M 256) rows, and of the logits of one full-width prefill. Both
# paths round x to bf16, dequantize the same int4 codes with the same f32
# formula and accumulate in f32, so a correct kernel differs at most in
# accumulation order (~1e-6); a wrong nibble, group or scale is off by
# order 1. The prefill is compiled with XLA's excess precision off: with
# it on, XLA skips bf16 roundings inside fusions, the kernel's custom
# calls change what fuses, and the two programs' logits part by ~2e-2
# after 24 random-weight layers with no fault in the kernel.
KERNEL_REL_TOL = 1e-3
EXACT_ROUNDING = {"xla_allow_excess_precision": False}

ARCH = "qwen25-05b"
PAGE = 16
CHUNK = 32
MAX_SEQ = 128
SLOTS = 8
PREFIX = 32                                     # shared system prompt
TAILS = (5, 21, 40, 60)                         # after the shared prefix
SOLO = (3, 17, 50, 100)                         # no prefix; 100 = 4 chunks
MAX_NEW = (24, 16, 20, 24, 28, 12, 24, 24)
KERNELS = ("awq_matmul", "paged_attention_chunk")


def log(msg: str) -> None:
    print(msg, flush=True)


def make_requests(vocab: int, seed: int):
    """(tokens, max_new, prefix_id) for 8 requests of mixed length; four
    share a 2-page system prompt."""
    import numpy as np
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(0, vocab, PREFIX, dtype=np.int32)
    reqs = []
    for n, new in zip(TAILS, MAX_NEW[:4]):
        tail = rng.integers(0, vocab, n, dtype=np.int32)
        reqs.append((np.concatenate([sys_prompt, tail]), new, "sys"))
    for n, new in zip(SOLO, MAX_NEW[4:]):
        reqs.append((rng.integers(0, vocab, n, dtype=np.int32), new, None))
    return reqs


def build(seed: int):
    """Published config, random init, AWQ calibrate + pack → (model,
    params)."""
    import jax

    import repro.configs as configs
    from repro.launch.serve import awq_quantize
    from repro.models import build_model
    cfg = configs.get_config(ARCH)
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = model.init(jax.random.PRNGKey(seed))
    params, report = awq_quantize(model, params, group_size=64)
    jax.block_until_ready(params)
    log(f"model: {cfg.name} {cfg.num_layers}L d={cfg.d_model} "
        f"heads={cfg.num_heads}/{cfg.num_kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size}; AWQ packed {len(report.quantized)} "
        f"linears in {time.perf_counter() - t0:.1f}s (init + calibrate + "
        f"pack)")
    return model, params


def kernel_counts(compiled_text: str) -> dict[str, int]:
    """tpu_custom_call instructions per kernel name in compiled HLO."""
    counts = dict.fromkeys(KERNELS, 0)
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            for name in KERNELS:
                if name in line:
                    counts[name] += 1
    return counts


def check_kernels(engine, label: str) -> None:
    for width in (1, CHUNK):
        text = engine.lowered_step(width).compile().as_text()
        counts = kernel_counts(text)
        log(f"{label}: serving step at width {width}: tpu_custom_call "
            + ", ".join(f"{k} x{v}" for k, v in counts.items()))
        missing = [k for k, v in counts.items() if v == 0]
        if missing:
            raise SystemExit(f"{label}: kernels missing from the compiled "
                             f"serving step: {missing}")


def check_linears(params, seed: int) -> None:
    """AWQ ``impl="kernel"`` vs ``impl="ref"`` on layer 0 of every packed
    linear, through the public `qlinear_apply`."""
    import jax
    import jax.numpy as jnp

    from repro.core.packing import PackedLinear
    from repro.core.qlinear import qlinear_apply
    from repro.utils.tree import path_str

    def packed(x):
        return isinstance(x, PackedLinear)

    apply = jax.jit(qlinear_apply, static_argnames="impl")
    key = jax.random.PRNGKey(seed)
    for path, p in jax.tree_util.tree_flatten_with_path(
            params, is_leaf=packed)[0]:
        if not packed(p):
            continue
        p0 = jax.tree.map(lambda a: a[0], p)          # layer 0 of the stack
        rels = []
        for m in (8, 256):
            x = jax.random.normal(key, (m, p0.k), jnp.float32)
            yk = apply(p0, x, impl="kernel")
            yr = apply(p0, x, impl="ref")
            rels.append(float(jnp.linalg.norm(yk - yr)
                              / jnp.linalg.norm(yr)))
        log(f"awq kernel vs ref: {path_str(path)} [{p0.k}, {p0.n}]: "
            f"relative error {rels[0]:.3e} at M 8, {rels[1]:.3e} at M 256 "
            f"(tol {KERNEL_REL_TOL})")
        if not all(r <= KERNEL_REL_TOL for r in rels):
            raise SystemExit(f"AWQ kernel and reference disagree on "
                             f"{path_str(path)}")


def padded_batch(seqs):
    import numpy as np
    toks = np.zeros((len(seqs), MAX_SEQ), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    return toks


def forward_fn(model, impl: str):
    """jit of the cache-free forward with the AWQ impl pinned at trace."""
    import jax

    from repro.core.qlinear import ExecutionConfig, execution_config

    def fwd(params, tokens):
        with execution_config(ExecutionConfig(impl=impl)):
            return model.forward_logits(params, {"tokens": tokens})
    return jax.jit(fwd)


def gap_fn(model):
    """jit: ``tokens [B, S]`` → per position, from one cache-free forward,
    (top logit, logit of the next token, std of the logits)."""
    import jax
    import jax.numpy as jnp

    fwd = forward_fn(model, "auto")

    @jax.jit
    def stats(params, tokens):
        logits = fwd(params, tokens)[:, :-1]            # predicts t+1
        nxt = tokens[:, 1:]
        chosen = jnp.take_along_axis(logits, nxt[..., None], -1)[..., 0]
        return logits.max(-1), chosen, logits.std(-1)
    return stats


def worst_gaps(stats, params, reqs, outs):
    """Worst (top - chosen) / std over each stream's generated tokens."""
    import jax
    import numpy as np
    seqs = [np.concatenate([p, o]) for (p, _, _), o in zip(reqs, outs)]
    top, chosen, std = jax.device_get(stats(params, padded_batch(seqs)))
    gaps = []
    for i, ((p, _, _), o) in enumerate(zip(reqs, outs)):
        sl = slice(len(p) - 1, len(p) - 1 + len(o))
        g = (top[i, sl] - chosen[i, sl]) / std[i, sl]
        gaps.append((float(g.max()), int((g == 0).sum()), len(o)))
    return gaps


def check_streams(model, params, reqs, outs, label: str, seed: int) -> None:
    import numpy as np
    stats = gap_fn(model)
    for i, (g, exact, n) in enumerate(worst_gaps(stats, params, reqs, outs)):
        log(f"{label}: stream {i}: {n} tokens, {exact} at the top logit, "
            f"worst gap {g:.4f} std (margin {TIE_MARGIN})")
        if not (len(outs[i]) == reqs[i][1] and g <= TIE_MARGIN):
            raise SystemExit(f"{label}: stream {i} fails the tie-tolerant "
                             f"check (gap {g:.4f}, {n} tokens)")
    rng = np.random.default_rng(seed + 1)
    junk = [rng.integers(0, model.cfg.vocab_size, len(o), dtype=np.int32)
            for o in outs]
    g = max(x[0] for x in worst_gaps(stats, params, reqs, junk))
    log(f"{label}: random-token control: worst gap {g:.3f} std")
    if g <= TIE_MARGIN:
        raise SystemExit("the stream check passes random tokens")


def serve(engine, reqs, label: str):
    """Submit every request, step to completion, drain; print smoke
    timings → outputs in request order."""
    t0 = time.perf_counter()
    rids = [engine.submit(p, new, prefix_id=pid) for p, new, pid in reqs]
    first: dict[int, float] = {}
    while not engine.idle:
        for rid, _ in engine.step():
            first.setdefault(rid, time.perf_counter() - t0)
    out = engine.drain()
    total = time.perf_counter() - t0
    outs = [out[r] for r in rids]
    ttft = sorted(first.values())
    log(f"{label}: served {len(rids)} requests, "
        f"{sum(len(o) for o in outs)} tokens out; first token after "
        f"{ttft[0]:.3f}s (median request {ttft[len(ttft) // 2]:.3f}s), "
        f"all done in {total:.3f}s [smoke reading, not a benchmark]")
    return outs


def engine_kwargs() -> dict:
    return dict(max_seq=MAX_SEQ, num_slots=SLOTS, page_size=PAGE,
                prefill_chunk=CHUNK, kv_quant="int8")


def one_chip(model, params, reqs, seed: int) -> None:
    import jax
    import numpy as np

    from repro.serving import GenerationEngine

    check_linears(params, seed)
    # AWQ kernel vs jnp reference on one full-width prefill
    toks = padded_batch([p for p, _, _ in reqs[:2]])
    t0 = time.perf_counter()
    lk, lr = (forward_fn(model, impl).lower(params, toks).compile(
        compiler_options=EXACT_ROUNDING)(params, toks)
        for impl in ("kernel", "ref"))
    rel = float(jax.numpy.linalg.norm(lk - lr) / jax.numpy.linalg.norm(lr))
    mx = float(jax.numpy.abs(lk - lr).max())
    log(f"awq kernel vs ref: prefill logits {tuple(lk.shape)}, excess "
        f"precision off: relative error {rel:.3e} (tol {KERNEL_REL_TOL}), "
        f"max abs diff {mx:.3e} [{time.perf_counter() - t0:.1f}s incl. "
        f"compile]")
    if not np.isfinite(rel) or rel > KERNEL_REL_TOL:
        raise SystemExit("AWQ kernel and reference logits disagree")

    engine = GenerationEngine(model, params, **engine_kwargs())
    t0 = time.perf_counter()
    n = engine.warmup()
    log(f"engine: warm-up compiled {n} step variants in "
        f"{time.perf_counter() - t0:.1f}s")
    check_kernels(engine, "engine")
    outs = serve(engine, reqs, "engine")
    check_streams(model, params, reqs, outs, "engine", seed)


def four_chips(model, params, reqs, seed: int) -> None:
    import jax

    from repro.launch.specs import FleetSpec, ReplicaSpec
    from repro.serving import GenerationEngine

    spec = FleetSpec(replicas=2, replica=ReplicaSpec(
        mesh_axis=2, engine_kwargs=engine_kwargs()))
    router = spec.build(model, params)
    t0 = time.perf_counter()
    n = router.warmup()
    log(f"fleet: warm-up compiled {n} step variants in "
        f"{time.perf_counter() - t0:.1f}s")
    held = []
    for i, eng in enumerate(router.replicas):
        where = eng.placement()
        log(f"fleet: replica {i}: params on "
            f"{sorted(d.id for d in where['params'])}, pools on "
            f"{sorted(d.id for d in where['pools'])}")
        if where["params"] != where["pools"] or len(where["params"]) != 2:
            raise SystemExit(f"replica {i} is not on one chip pair")
        held.append(where["params"])
    if held[0] & held[1] or held[0] | held[1] != set(jax.devices()):
        raise SystemExit("replicas overlap or leave a chip unused")
    check_kernels(router.replicas[0], "fleet replica 0")
    outs = serve(router, reqs, "fleet")
    check_streams(model, params, reqs, outs, "fleet", seed)

    single = GenerationEngine(model, params, **engine_kwargs())
    single.warmup()
    ref = serve(single, reqs, "one-chip engine")
    same = sum(len(a) == len(b) and (a == b).all() for a, b in zip(outs, ref))
    log(f"fleet vs one-chip engine: {same}/{len(reqs)} streams "
        f"token-identical (the rest part at a near-tie, see check above)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2 x TP-2 fleet phase on 4 chips")
    args = ap.parse_args(argv)

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: the repro package is not at {src}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    from repro.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    devices = jax.devices()
    need = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chip(s), JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"device: {device['kind']} x{device['count']}; compile cache "
        f"{cache_dir}")

    t0 = time.perf_counter()
    model, params = build(args.seed)
    reqs = make_requests(model.cfg.vocab_size, args.seed)
    if args.four_chips:
        four_chips(model, params, reqs, args.seed)
    else:
        one_chip(model, params, reqs, args.seed)
    log(f"smoke passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
