"""Open-loop tail-latency benchmark for the async request plane.

The closed-loop bench (``bench_range_query``) answers "how fast can
the server chew pre-formed batches"; this one answers the serving
question: under an **open-loop** arrival stream — seeded Poisson
arrivals that keep coming whether or not earlier requests finished —
what latency does a single request see through queueing + batch
forming + execution, and what throughput does the plane sustain?

Per (placement × offered load) the run drives
``frontend.simulate_open_loop``: arrivals and every plane decision
(admission, DRR, deadline-or-full closing) happen in deterministic
virtual time from one seed, while each formed batch is executed for
real against the ``SpatialServer`` and its measured wall service time
advances the virtual clock (single-server queueing model).  Reported
rows carry p50/p99 queue/total latency, sustained QPS, batch fill
ratio, and the admission counters.  Exactness is asserted: every
response must equal the direct batched call for its query.

``--smoke`` shrinks the dataset and stream for CI.  ``--json`` merges
a ``frontend`` section into ``BENCH_serving.json`` (written by
``bench_range_query --json``; run that first in CI) rather than
clobbering the closed-loop rows.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compat import use_compile_cache
from repro.data import spatial_gen
from repro.serve import ServeConfig, SpatialServer
from repro.serve.frontend import (FrontendConfig, poisson_workload,
                                  simulate_open_loop)

from .common import emit

JSON_PATH = pathlib.Path(__file__).resolve().parents[1] / "BENCH_serving.json"


def _qboxes(rng, q, scale=0.05):
    c = rng.uniform(0, 1, (q, 2)).astype(np.float32)
    s = rng.uniform(0, scale, (q, 2)).astype(np.float32)
    return np.concatenate([c - s, c + s], axis=-1)


def _workload(qboxes, pts, rate, duration, seed):
    """80% range_counts / 20% knn mix over a pooled query set, with a
    70%-hot tenant skew — the shape of real multi-tenant traffic."""
    nq, npt = qboxes.shape[0], pts.shape[0]

    def make(rng, i):
        tenant = "hot" if rng.random() < 0.7 else f"t{i % 4}"
        if rng.random() < 0.8:
            return "range_counts", qboxes[i % nq], (), tenant
        return "knn", pts[i % npt], (8, 512), tenant

    return poisson_workload(rate, duration, make, seed=seed)


def _verify(server, workload, responses, want_counts, want_knn, nq, npt):
    """Every OK response must be bit-identical to the direct batched
    call for its query (the frontend exactness bar)."""
    for i, (a, r) in enumerate(zip(workload, responses)):
        if not r.ok:
            continue
        if a.kind == "range_counts":
            assert r.value == want_counts[i % nq], (i, r.value)
        else:
            nn_ids, nn_d2, _ = r.value
            np.testing.assert_array_equal(nn_ids, want_knn[0][i % npt])
            np.testing.assert_array_equal(nn_d2, want_knn[1][i % npt])


def main(smoke: bool = False, json_out: bool = False) -> None:
    n, payload = (1500, 130) if smoke else (6000, 120)
    duration = 0.25 if smoke else 1.0
    rates = (2000.0,) if smoke else (1000.0, 4000.0, 16000.0)
    fcfg = FrontendConfig(ladder=(64, 128, 256, 512), max_delay=0.002)

    mbrs = spatial_gen.dataset("osm", jax.random.PRNGKey(0), n)
    rng = np.random.default_rng(42)
    qboxes = _qboxes(rng, 64)
    pts = rng.uniform(0, 1, (64, 2)).astype(np.float32)

    sections = []
    for placement in ("replicated", "sharded"):
        cfg = (ServeConfig() if placement == "replicated"
               else ServeConfig(placement="sharded", shards=4))
        srv = SpatialServer.from_method("bsp", mbrs, payload, cfg)
        want_counts = [int(c) for c in
                       np.asarray(srv.range_counts(jnp.asarray(qboxes))[0])]
        nn_w, d2_w, _, _ = srv.knn(jnp.asarray(pts), 8, max_cand=512)
        want_knn = (np.asarray(nn_w), np.asarray(d2_w))
        # warm the compiled ladder widths so the open-loop run measures
        # serving, not first-batch compilation
        for w in fcfg.ladder:
            srv.range_counts(jnp.zeros((w, 4), jnp.float32))
            srv.knn(jnp.zeros((w, 2), jnp.float32), 8, max_cand=512)

        for rate in rates:
            wl = _workload(qboxes, pts, rate, duration, seed=7)
            t0 = time.perf_counter()
            responses, metrics = simulate_open_loop(srv, wl, fcfg)
            wall_s = time.perf_counter() - t0
            _verify(srv, wl, responses, want_counts, want_knn,
                    qboxes.shape[0], pts.shape[0])
            snap = metrics.snapshot()
            done = snap["completed"]
            # sustained QPS: completions over the virtual makespan (the
            # open-loop clock the latencies are measured on)
            makespan = max((r.total_s + a.t for a, r in
                            zip(wl, responses) if r.ok), default=0.0)
            qps = done / makespan if makespan else 0.0
            row = dict(
                placement=placement, offered_qps=rate,
                requests=len(wl), completed=done,
                rejected=snap["rejected"], timed_out=snap["timed_out"],
                sustained_qps=round(qps, 1),
                p50_ms=round(snap["total_s"]["p50"] * 1e3, 3),
                p99_ms=round(snap["total_s"]["p99"] * 1e3, 3),
                queue_p99_ms=round(snap["queue_s"]["p99"] * 1e3, 3),
                execute_p99_ms=round(snap["execute_s"]["p99"] * 1e3, 3),
                batches=snap["batches"],
                batch_fill_ratio=snap["batch_fill_ratio"],
                padded_slots=snap["padded_slots"],
                queue_depth_max=snap["queue_depth_max"],
                wall_s=round(wall_s, 3),
            )
            sections.append(row)
            emit(f"frontend_open_loop/{placement}/rate{rate:.0f}",
                 snap["total_s"]["p50"] * 1e6,
                 f"p99_ms={row['p99_ms']};qps={row['sustained_qps']}"
                 f";fill={row['batch_fill_ratio']}"
                 f";rejected={row['rejected']}"
                 f";timed_out={row['timed_out']}"
                 f";batches={row['batches']}")

    if json_out:
        doc = json.loads(JSON_PATH.read_text()) if JSON_PATH.exists() else {}
        doc["frontend"] = dict(
            smoke=smoke, n_objects=n, duration_s=duration,
            max_delay_s=fcfg.max_delay, ladder=list(fcfg.ladder),
            backend=jax.default_backend(), rows=sections)
        JSON_PATH.write_text(json.dumps(doc, indent=2) + "\n")
        print(f"# merged frontend section into {JSON_PATH}",
              file=sys.stderr)


if __name__ == "__main__":
    use_compile_cache(str(pathlib.Path(__file__).resolve().parents[1]))
    main(smoke="--smoke" in sys.argv, json_out="--json" in sys.argv)
