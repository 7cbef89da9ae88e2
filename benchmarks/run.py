"""Benchmark runner — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (stdout), one per cell.
"""
from __future__ import annotations

import os
import sys
import traceback

from repro.core.compat import use_compile_cache

from . import (bench_balanced_batch, bench_cost_model, bench_join,
               bench_kernels, bench_paper_hillclimb,
               bench_parallel_partition, bench_partition_runtime,
               bench_quality, bench_range_query, bench_sampling)

ALL = {
    "quality": bench_quality,            # Figs 3 & 4
    "join": bench_join,                  # Fig 5
    "range_query": bench_range_query,    # §6 selection workloads
    "partition_runtime": bench_partition_runtime,   # Figs 6 & 7
    "parallel_partition": bench_parallel_partition,  # Fig 8
    "sampling": bench_sampling,          # Fig 9
    "cost_model": bench_cost_model,      # §2.3
    "kernels": bench_kernels,            # Pallas microbenches
    "balanced_batch": bench_balanced_batch,          # LM integration
    "paper_hillclimb": bench_paper_hillclimb,        # §Perf cell 3
}


def main() -> None:
    use_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    which = sys.argv[1:] or list(ALL)
    print("name,us_per_call,derived")
    failed = []
    for name in which:
        try:
            ALL[name].main()
        except Exception:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc(file=sys.stderr)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
