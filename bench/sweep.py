"""Find a cell's knee: the highest offered rate whose backlog does not
grow over a window.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> \\
        --rates 400,800,1200

One process stages the cell's deployment once and warms it (with
every rate's requests, so every shape is warm), then offers the
cell's open-loop mix at each rate for ``--seconds`` through a fresh
``ServeFrontend``.  For each rate it prints one JSON line: the
latencies, the share answered inside the window, and ``growth``, the
median latency of the requests due in the window's last fifth over
that of the second fifth.  A backlog that grows shows as ``growth``
well above 1 and answers falling behind the offered rate.  A rate is
past the knee where ``growth`` exceeds ``GROWTH`` or fewer than
``ANSWERED`` of its requests are answered inside the window; the
sweep stops after two such rates in a row, and the last line names
the knee, the highest rate swept below the first of them.  The rate
in a cell's traffic file is then fixed at about four fifths of the
knee.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
GROWTH = 1.25
ANSWERED = 0.95


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import numpy as np

    from bench import gen, harness, load

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    cell, cfg, traffic, _, _ = harness.cell_spec(benchmark, args.workload)
    if traffic["loop"] != "open":
        sys.exit(f"sweep: {args.workload} is not an open-loop cell")
    from repro.core.compat import use_compile_cache
    import jax
    from repro.serve.frontend import FrontendConfig, ServeFrontend

    use_compile_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit("sweep: no TPU; the sweep runs on the chip only")
    rates = [float(r) for r in args.rates.split(",")]
    mbrs = gen.dataset(cfg, args.seed)
    mbrs_np = np.asarray(mbrs)
    server, partition_s = harness.build_server(jax, cfg, mbrs,
                                               devices[:cell["chips"]])
    boxes = np.asarray(server.parts.boxes)[np.asarray(server.parts.valid)]
    fe_config = FrontendConfig(**traffic.get("frontend", {}))
    plans = {}
    for rate in rates:
        mix = dict(traffic, rate=rate)
        plans[rate] = harness.requests(cfg, mix, args.seed, args.seconds,
                                       mbrs_np, boxes)
    harness.warm_up(server, traffic, sum(plans.values(), []),
                    fe_config.ladder)
    harness.settle()
    print(json.dumps({"setup_s": time.perf_counter() - T_START,
                      "partition_s": partition_s, **server.stats}),
          flush=True)

    async def window(reqs, mix):
        async with ServeFrontend(server, fe_config) as fe:
            t0 = time.perf_counter()
            late = await load.open_loop(fe, reqs, mix, t0, harness.DRAIN_S)
        return t0, late

    knee, past, crossed = None, 0, False
    for rate in rates:
        reqs, mix = plans[rate], dict(traffic, rate=rate)
        t0, late = asyncio.run(window(reqs, mix))
        lat = np.asarray([(r.done if r.done >= 0 else np.inf)
                          - (t0 + r.due) for r in reqs])
        fifth = len(reqs) // 5
        growth = (np.median(lat[-fifth:]) / np.median(lat[fifth:2 * fifth])
                  if fifth else float("nan"))
        answered = sum(1 for r in reqs
                       if 0 <= r.done <= t0 + args.seconds) / len(reqs)
        print(json.dumps({"rate": rate, "requests": len(reqs),
                          "p50_ms": float(np.percentile(lat, 50)) * 1e3,
                          "p99_ms": float(np.percentile(lat, 99)) * 1e3,
                          "answered_in_window": answered,
                          "growth": float(growth),
                          "generator_late_ms": late * 1e3}), flush=True)
        if growth > GROWTH or answered < ANSWERED:
            crossed, past = True, past + 1
            if past == 2:
                break
        else:
            past = 0
            if not crossed:
                knee = rate
    print(json.dumps({"knee": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
