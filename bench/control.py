"""The control of the check that decides ``correct``.

    python3 bench/control.py --workload <name> --seeds 1,2,3

For each seed this makes the cell's objects and requests exactly as a
run of ``run.py`` does, puts the control (``oracle.control_answers``:
the reference on bfloat16-rounded objects and queries) in the
program's place, and holds its answers to the same comparison, on the
same seeded sample.  It prints one JSON line per seed with each number
compared and ``correct``, which has to come out false.  It needs no
measured window: the control answers every request directly.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def control_numbers(jax, cfg: dict, traffic: dict, seed: int,
                    seconds: float) -> dict:
    """The compared numbers of the control on one seed."""
    import numpy as np

    from bench import gen, harness, oracle
    from repro.core.partition import api

    mbrs = gen.dataset(cfg, seed)
    mbrs_np = np.asarray(mbrs)
    parts = api.partition(cfg["partitioner"], mbrs, cfg["payload"])
    boxes = np.asarray(parts.boxes)[np.asarray(parts.valid)]
    reqs = harness.requests(cfg, traffic, seed, seconds, mbrs_np, boxes)
    picked = harness.sample(np.random.default_rng(seed + 1), reqs, traffic)
    queries = {kind: np.stack([r.payload for r in rs])
               for kind, rs in picked.items()}
    return oracle.compare(mbrs_np, oracle.control_answers(
        mbrs_np, queries, traffic.get("max_hits", 1024),
        traffic.get("k", 10)), 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="the window whose requests are sampled "
                         "(default: BENCHMARK.json's run_seconds)")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness, oracle
    import jax

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    _, cfg, traffic, _, _ = harness.cell_spec(benchmark, args.workload)
    seconds = args.seconds or benchmark["run_seconds"]
    for seed in (int(s) for s in args.seeds.split(",")):
        numbers = control_numbers(jax, cfg, traffic, seed, seconds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": oracle.verdict(numbers),
                          "numbers": numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
