"""Benchmark harness — one module per paper table/figure.

  Table I   → bench_latency_breakdown (MAC-share of decode latency)
  Table III → bench_compression (model size), bench_throughput (tok/s +
              Eq. 1 score), bench_accuracy (quantization quality proxy)
  Table II  → bench_kernels (structural accelerator numbers)
  §Roofline → roofline (aggregated dry-run terms, if results exist)

Prints ``name,us_per_call,derived`` CSV rows.
"""
from __future__ import annotations

import os
import traceback


def main() -> None:
    rows: list[tuple[str, str, str]] = []
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_accuracy, bench_compression,
                            bench_kernels, bench_latency_breakdown,
                            bench_serving, bench_throughput)
    modules = [
        ("latency_breakdown", bench_latency_breakdown),
        ("compression", bench_compression),
        ("accuracy", bench_accuracy),
        ("throughput", bench_throughput),
        ("serving", bench_serving),
        ("kernels", bench_kernels),
    ]
    failures = []
    for name, mod in modules:
        try:
            mod.run(rows)
        except Exception as e:
            failures.append((name, repr(e)))
            traceback.print_exc()
    if os.path.isdir(os.environ.get("DRYRUN_DIR", "results/dryrun")):
        try:
            from benchmarks import roofline
            roofline.run(rows)
        except Exception as e:
            failures.append(("roofline", repr(e)))

    print("name,us_per_call,derived")
    for r in rows:
        print(",".join(str(x) for x in r))
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
