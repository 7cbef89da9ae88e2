"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a deployment
(``bench/configs/``) and a traffic mix (``bench/traffic/``).  The run
makes the deployment's objects from ``--seed`` on the chip, partitions
and stages them through ``SpatialServer``, warms every batch shape the
traffic uses, then drives ``ServeFrontend`` for ``--seconds`` and
checks a seeded sample of the window's answers against the
benchmark's own reference (``bench/oracle.py``).  The last line of
stdout is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``,
each number compared with its limit.  It runs on TPUs only: without
one, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        sys.exit(f"bench: {ROOT} holds no src/repro, the program under "
                 f"test; run from a checkout of the repository")
    sys.path.insert(0, ROOT)
    sys.path.insert(1, os.path.join(ROOT, "src"))
    from bench import harness

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    cell, cfg, traffic, e2e, per_layer = harness.cell_spec(
        benchmark, args.workload)

    from repro.core.compat import use_compile_cache
    import jax

    use_compile_cache(ROOT)
    # every program goes to the cache, so that only a checkout's first
    # run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: no TPU (JAX platform {devices[0].platform!r}); "
                 f"the benchmark runs on the chip only")
    if len(devices) < cell["chips"]:
        sys.exit(f"bench: {args.workload} needs {cell['chips']} chips, "
                 f"JAX sees {len(devices)}")
    out = harness.run_cell(
        jax, cfg, traffic, args.seed, args.seconds, bool(args.trace),
        per_layer if args.trace else e2e, T_START,
        devices[:cell["chips"]])
    print(harness.result_line(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
