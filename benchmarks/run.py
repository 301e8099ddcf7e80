"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Mapping (DESIGN.md §7):
    bench_load_ratio      → Eqs. 4/8 (exact byte accounting)
    bench_recall          → Figs. 3 & 6 (1-bit top-k recall vs Quest)
    bench_ablation        → Tab. 3 (granularity × quantized scoring)
    bench_latency         → Fig. 8 (decode latency trend + v5e byte model)
    bench_pg19            → Fig. 5 (ppl vs context under budgets; proxy)
    bench_passkey         → Tab. 2 (passkey accuracy vs budget)
    bench_longbench_proxy → Fig. 7 / Tab. 1 (multi-needle QA; proxy)

Roofline (§Roofline/§Perf) is separate: ``python -m benchmarks.roofline``
(needs the 512-device dry-run environment).
"""
from __future__ import annotations

import sys
import time
import traceback

from repro.launch.compile_cache import enable_compilation_cache

MODULES = [
    "bench_load_ratio",
    "bench_recall",
    "bench_ablation",
    "bench_latency",
    "bench_pg19",
    "bench_passkey",
    "bench_longbench_proxy",
]


def main() -> None:
    enable_compilation_cache()
    print("name,us_per_call,derived")
    failures = []
    for name in MODULES:
        t0 = time.time()
        print(f"# --- {name} ---")
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            mod.run()
            print(f"# {name} done in {time.time()-t0:.1f}s")
        except Exception:
            traceback.print_exc()
            failures.append(name)
    if failures:
        print(f"# FAILURES: {failures}")
        sys.exit(1)
    print("# all benchmarks complete")


if __name__ == "__main__":
    main()
