"""Benchmark orchestrator — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run           # full
    BENCH_ONLY=fig3 PYTHONPATH=src python -m benchmarks.run

Output format: ``name,us_per_call,derived`` CSV rows on stdout.
"""
from __future__ import annotations

import os
import sys
import traceback


def main() -> None:
    only = os.environ.get("BENCH_ONLY")
    sections = [
        ("table1", "benchmarks.table1_graphs"),
        ("core", "benchmarks.core_bench"),
        ("mem", "benchmarks.memory_footprint"),
        ("fig3", "benchmarks.fig3_quality"),
        ("fig4", "benchmarks.fig4_runtime"),
        ("kernel", "benchmarks.kernel_bench"),
        ("hybrid", "benchmarks.hybrid_bench"),
        ("serve", "benchmarks.serve_throughput"),
        ("dyngraph", "benchmarks.dyngraph_bench"),
    ]
    failures = 0
    for name, module in sections:
        if only and only != name:
            continue
        print(f"# --- {name} ({module}) ---", flush=True)
        try:
            mod = __import__(module, fromlist=["main"])
            mod.main()
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"# SECTION FAILED: {name}", flush=True)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
