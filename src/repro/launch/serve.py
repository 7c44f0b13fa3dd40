"""Serving launcher: AWQ-quantize a model and serve batched requests.

The end-to-end path of the paper (§III-A "fully automated"): init (or load)
float params → calibration forward → AWQ search + pack (GS=64 INT4) → serve
with the fused dequant-matmul path. ``--quant none`` serves the float
baseline (the paper's 2.8 tok/s side of Table III). Requests go through
the continuous-batching engine (`submit`/`drain`) over int8 paged KV —
on a TPU that is the path where both Pallas kernels (AWQ matmul and
paged attention) run, the one ``chip_smoke.py`` checks.

With ``--replicas N`` (or any fleet flag) the launcher serves a
continuous-batching **fleet** instead: N `GenerationEngine` replicas —
each ``--mesh-axis``-wide TP, or ``--disagg`` prefill/decode pairs —
behind the prefix-affinity `serving.Router`, built declaratively from
`launch.specs.FleetSpec` (the k8s-style deployment description:
replica count, per-replica mesh shape, drain timeout).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen25-05b --smoke \
      --batch 4 --prompt-len 32 --max-new 32 --quant awq
  PYTHONPATH=src python -m repro.launch.serve --arch qwen25-05b --smoke \
      --replicas 2 --mesh-axis 1 --quant none
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
from repro.core import (AWQConfig, CalibrationCapture, QuantConfig,
                        quantize_params)
from repro.core.pipeline import model_size_bytes
from repro.data import make_dataset
from repro.launch.specs import FleetSpec, ReplicaSpec
from repro.models import build_model
from repro.serving import GenerationEngine, SamplerConfig
from repro.utils.compile_cache import enable_compile_cache


def awq_quantize(model, params, group_size: int = 64):
    """Calibrate on a synthetic batch and AWQ-pack every quantizable
    linear → (params, `PTQReport`)."""
    cfg = model.cfg
    ds = make_dataset(cfg, 2, min(64, cfg.max_seq_len), seed=123)
    calib = {k: jnp.asarray(v) for k, v in ds.batch_at(0).items()}
    with CalibrationCapture() as cap:
        model.loss(params, calib)
    qcfg = AWQConfig(quant=QuantConfig(group_size=group_size))
    return quantize_params(params, cap.stats, qcfg)


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen25-05b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--quant", default="awq", choices=["awq", "none"])
    ap.add_argument("--group-size", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    # fleet flags (k8s-style: scale + pod template + drain budget)
    ap.add_argument("--replicas", type=int, default=0,
                    help="serve a Router fleet of N replicas instead of "
                         "one engine (0 = one engine)")
    ap.add_argument("--mesh-axis", type=int, default=1,
                    help="per-replica tensor-parallel 'model' axis width")
    ap.add_argument("--disagg", action="store_true",
                    help="each replica is a prefill/decode engine pair")
    ap.add_argument("--drain-timeout", type=float, default=30.0,
                    help="drain_replica step budget (seconds) for elastic "
                         "scale-down")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    print(f"[serve] {cfg.name}: fp16-serialized size "
          f"{model_size_bytes(params, quantized=False)/1e6:.2f} MB")

    if args.quant == "awq":
        t0 = time.time()
        params, report = awq_quantize(model, params, args.group_size)
        print(f"[serve] AWQ PTQ in {time.time()-t0:.1f}s: "
              f"{len(report.quantized)} linears quantized "
              f"({len(report.calibrated)} calibrated), "
              f"{len(report.skipped)} kept FP")
        print(f"[serve] AWQ_MACRO-serialized size "
              f"{model_size_bytes(params, quantized=True)/1e6:.2f} MB")

    if args.replicas > 0:
        return serve_fleet(model, params, args)
    return serve_batch(model, params, args)


def serve_batch(model, params, args) -> dict:
    """One continuous-batching engine: ``--batch`` prompts submitted at
    once and drained, after a warm-up that compiles the step family."""
    page = 16
    max_seq = args.prompt_len + args.max_new
    max_seq += -max_seq % page
    engine = GenerationEngine(
        model, params, max_seq=max_seq, num_slots=args.batch,
        page_size=page, prefill_chunk=page, kv_quant="int8",
        sampler=SamplerConfig(temperature=args.temperature))
    ds = make_dataset(model.cfg, args.batch, args.prompt_len, seed=args.seed)
    prompts = np.asarray(ds.batch_at(0)["tokens"])

    t0 = time.time()
    variants = engine.warmup(sampled=args.temperature > 0)
    compile_s = time.time() - t0
    t0 = time.time()
    rids = [engine.submit(p, args.max_new) for p in prompts]
    out = engine.drain()
    dt = time.time() - t0
    useful = sum(len(out[r]) for r in rids)
    tput = useful / dt
    print(f"[serve] warm-up compiled {variants} step variants in "
          f"{compile_s:.1f}s")
    print(f"[serve] served {len(rids)} requests / {useful} tokens in "
          f"{dt:.2f}s ({tput:.1f} tok/s wall on {jax.default_backend()})")
    print(f"[serve] sample: {out[rids[0]][:16].tolist()}")
    return {"tokens_per_s": tput, "requests": len(rids),
            "compile_s": compile_s}


def serve_fleet(model, params, args) -> dict:
    """Continuous-batching fleet: FleetSpec → Router → clustered burst.

    The burst shares one system prefix per cluster so the router's
    prefix-affinity scoring has something to aim at; the report prints
    per-replica prefill-skip and queue-depth so placement is visible.
    """
    cfg = model.cfg
    max_seq = args.prompt_len + args.max_new
    page = 8
    spec = FleetSpec(
        replicas=args.replicas,
        replica=ReplicaSpec(
            mesh_axis=args.mesh_axis, disagg=args.disagg,
            prefill_mesh_axis=args.mesh_axis,
            decode_mesh_axis=args.mesh_axis,
            engine_kwargs=dict(max_seq=max_seq, num_slots=args.batch,
                               page_size=page, prefill_chunk=page,
                               kv_quant="int8")),
        drain_timeout_s=args.drain_timeout)
    print(f"[serve] fleet: {spec.replicas} replica(s), mesh_axis="
          f"{args.mesh_axis}, disagg={args.disagg}, "
          f"drain_timeout={spec.drain_timeout_s:.0f}s")
    router = spec.build(model, params)
    router.warmup()

    rng = np.random.default_rng(args.seed)
    n_clusters = 2
    prefixes = [rng.integers(0, cfg.vocab_size, (args.prompt_len - 4,)
                             ).astype(np.int32) for _ in range(n_clusters)]
    # pin first (sticky), then warm one request per cluster so the burst
    # below has resident prefixes to route toward
    for c in range(n_clusters):
        router.pin_prefix(f"sys{c}")
        router.submit(np.concatenate(
            [prefixes[c],
             rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)]),
            2, prefix_id=f"sys{c}")
    router.drain()
    n_req = max(args.batch * args.replicas, 4)
    rids = []
    t0 = time.time()
    for i in range(n_req):
        c = i % n_clusters
        tail = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
        rids.append(router.submit(
            np.concatenate([prefixes[c], tail]), args.max_new,
            sampler=SamplerConfig(temperature=args.temperature),
            prefix_id=f"sys{c}", session_id=f"user{i % (2 * n_clusters)}"))
    out = router.drain()
    dt = time.time() - t0
    useful = sum(len(out[r]) for r in rids)
    tput = useful / dt
    skipped = sum(getattr(s, "prefill_tokens_skipped", 0)
                  for s in router.stats())  # DisaggStats has no such field
    print(f"[serve] fleet served {n_req} requests / {useful} tokens in "
          f"{dt:.2f}s ({tput:.1f} tok/s wall on {jax.default_backend()})")
    print(f"[serve] placement: {router.router_stats.placements} scored, "
          f"{router.router_stats.affinity_hits} affinity hits, "
          f"{router.router_stats.session_hits} session hits, "
          f"{skipped} prefill tokens skipped fleet-wide")
    return {"tokens_per_s": tput, "requests": n_req,
            "prefill_tokens_skipped": int(skipped),
            "replicas": args.replicas}


if __name__ == "__main__":
    main()
