"""Time the serving step and the paged-attention kernel over the context
buckets, on the chip.

    python scripts/page_sweep.py --config qwen25-7b-awq [--widths 1,16]
                                 [--pages 8,16,32,64,128] [--no-step]

For each context bucket (pages a row reads) and chunk width, over the
configuration's slots:

  * ``kernel_ms``: one `paged_attention_chunk` call at one layer's shapes,
    over an int8 pool of the configuration's size (every slot at full
    length), page tables drawn at random, every row's chunk at the end of
    its bucket. The time is the host's, dispatch included (about 0.6 ms a
    call on a TPU v5e host); kernel roofline shares come from a device
    trace (`bench/trace.py`), not from here.
  * ``step_ms``: one whole greedy step variant of the engine as the
    benchmark builds it (its weights from ``--seed``), on all-padding rows:
    the kernel reads every page of the bucket whatever the rows hold.

Each time is the median of ``--reps`` calls after a compile call, each
ended by ``block_until_ready``. One JSON line per (bucket, width). Run it
on a TPU, in a process of its own: it exits with status 1 on any other
backend, whose times would measure the engine's CPU paths.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def _median_ms(fn, reps: int) -> float:
    import jax
    jax.block_until_ready(fn())                 # compile, warm
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn())
        times.append((time.perf_counter() - t) * 1e3)
    return statistics.median(times)


def kernel_ms(cfg: dict, n_pages: int, width: int, reps: int) -> float:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention import paged_attention_chunk

    s = cfg["serving"]
    b, page = s["num_slots"], s["page_size"]
    hkv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    g = cfg["num_attention_heads"] // hkv
    pool_pages = b * (s["max_seq"] // page) + 1
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.integers(-127, 128, (pool_pages, hkv, page, hd)),
                    jnp.int8)
    v = jnp.asarray(rng.integers(-127, 128, (pool_pages, hkv, page, hd)),
                    jnp.int8)
    ks = jnp.asarray(rng.uniform(0.001, 0.01, (pool_pages, hkv, page)),
                     jnp.float32)
    vs = jnp.asarray(rng.uniform(0.001, 0.01, (pool_pages, hkv, page)),
                     jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, width, hkv, g, hd)), jnp.bfloat16)
    table = jnp.asarray(rng.permutation(pool_pages - 1)[:b * n_pages]
                        .reshape(b, n_pages) + 1, jnp.int32)
    end = n_pages * page - width
    pos = jnp.broadcast_to(jnp.arange(end, end + width, dtype=jnp.int32),
                           (b, width))
    fn = jax.jit(paged_attention_chunk)
    return _median_ms(lambda: fn(q, k, ks, v, vs, table, pos), reps)


def step_sweep(cfg: dict, seed: int, pages: list[int], widths: list[int],
               reps: int) -> dict:
    import jax.numpy as jnp

    from bench import sut, weights

    w = weights.make_weights(cfg, seed)
    engine, _ = sut.build(cfg, w)
    engine.warmup()
    b = engine.num_slots
    out = {}
    for nb in pages:
        tables = engine._device_tables(nb)
        for c in widths:
            args = (jnp.zeros((b, c), jnp.int32),
                    jnp.full((b, c), -1, jnp.int32),
                    jnp.zeros(b, jnp.int32), jnp.zeros(b, jnp.int32))

            def step():
                toks, engine._paged_cache = engine._chunk_greedy(
                    engine._params_run, engine._paged_cache, tables, *args)
                return toks
            out[nb, c] = _median_ms(step, reps)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="a file name under bench/configs, without .json, "
                         "or the path of a configuration file")
    ap.add_argument("--widths", default="1,16")
    ap.add_argument("--pages", default="8,16,32,64,128")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-step", action="store_true",
                    help="time the kernel alone")
    args = ap.parse_args(argv)
    import jax
    if jax.default_backend() != "tpu":
        print(f"page_sweep: needs a TPU, found {jax.default_backend()}",
              file=sys.stderr)
        return 1
    path = Path(args.config)
    if path.suffix != ".json":
        path = ROOT / "bench" / "configs" / f"{args.config}.json"
    cfg = json.loads(path.read_text())
    pages = [int(x) for x in args.pages.split(",")]
    widths = [int(x) for x in args.widths.split(",")]
    dev = jax.devices()[0]
    steps = {} if args.no_step else step_sweep(cfg, args.seed, pages,
                                               widths, args.reps)
    for nb in pages:
        for c in widths:
            line = {"config": cfg["name"], "device": dev.device_kind,
                    "pages": nb, "width": c,
                    "kernel_ms": kernel_ms(cfg, nb, c, args.reps)}
            if (nb, c) in steps:
                line["step_ms"] = steps[nb, c]
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
