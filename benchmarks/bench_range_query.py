"""Range/kNN serving throughput across all six layouts × both datasets,
pruned (routed candidate-tile probe, with the intra-tile local index)
vs unindexed (``ServeConfig(local_index="off")``, same routing, linear
tile sweep) vs dense (all-tile oracle sweep) vs sharded (owner-routed
all_to_all exchange) — the paper's layout-quality thesis measured as
queries/sec, not just mean fan-out: the better the layout routes, the
smaller each query's candidate list and the larger the pruned speedup;
the local index then skips dead 128-member chunks *inside* each
candidate tile (chunk-skip rate reported per layout, for the default
``"x"`` sort and the ``"hilbert"`` sort — square-ish chunk boxes vs
x-strips).  Streaming rows time ``append`` throughput into reserved
slack (and the scattered device bytes per appended object — the O(M)
ingest bar: flat per object, independent of the T×cap layout size) and
the cost of a forced tile-overflow re-stage.  The
``interleaved_stream`` scenario runs a sustained append/delete/update/
query mix against one server and reports ingest ops/sec and the query
p50 under churn (with the compaction policy live).  The ``heat_plan``
rows replay a skewed hotspot stream and compare exchange messages under
the count-balanced shard plan, after heat-aware co-location of the same
server, and on a ``placement="heat"`` server (co-location + hot-tile
replicas) — with bit-identity asserted against the dense reference on
every leg, and a hard check on ``osm`` that co-location never adds
exchange traffic.

``--smoke`` runs a small configuration (CI: exercises the pruned,
local-index, and sharded paths and the exactness assertions on every
push without the full timing).  ``--devices N`` forces N virtual host
devices (``--xla_force_host_platform_device_count``) so the sharded
rows run the real mesh exchange; without it the exchange runs in
simulation over 4 virtual owners.  ``--json`` additionally writes
``BENCH_serving.json`` at the repo root (queries/sec, fan-out,
chunk-skip rate per layout × dataset) so the perf trajectory is
recorded run over run; CI uploads it as an artifact.
"""
from __future__ import annotations

import json
import os
import pathlib
import sys
import time

if __name__ == "__main__" and "--devices" in sys.argv:
    _n = int(sys.argv[sys.argv.index("--devices") + 1])
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count={_n}")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compat import use_compile_cache
from repro.data import spatial_gen
from repro.query import range as range_mod
from repro.serve import PlacementPolicy, ServeConfig, SpatialServer

from .common import emit, timeit, timeit_many

METHODS = ["fg", "bsp", "slc", "bos", "str", "hc"]
DATASETS = ["osm", "pi"]
JSON_PATH = pathlib.Path(__file__).resolve().parents[1] / "BENCH_serving.json"


def _qboxes(key, q, scale=0.05):
    k1, k2 = jax.random.split(key)
    c = jax.random.uniform(k1, (q, 2))
    s = jax.random.uniform(k2, (q, 2)) * scale
    return jnp.concatenate([c - s, c + s], axis=-1)


def _hot_qboxes(key, q, frac=0.85, hot_scale=0.14, scale=0.05):
    """Skewed query stream for the heat-placement rows: ``frac`` of the
    query centres cluster inside one small hotspot patch and carry
    larger boxes (``hot_scale``), so each hot query's candidates span
    several tiles — the multi-owner fan-out that co-location + hot-tile
    replicas exist to collapse.  The rest stay uniform."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    n_hot = int(q * frac)
    ctr = jax.random.uniform(k1, (2,)) * 0.6 + 0.2
    c_hot = ctr + (jax.random.uniform(k2, (n_hot, 2)) - 0.5) * 0.2
    c_cold = jax.random.uniform(k3, (q - n_hot, 2))
    c = jnp.concatenate([c_hot, c_cold], axis=0)
    s = jax.random.uniform(k4, (q, 2)) * scale
    s = s.at[:n_hot].set(
        jax.random.uniform(jax.random.fold_in(k4, 1), (n_hot, 2))
        * hot_scale + 0.02)
    return jnp.concatenate([c - s, c + s], axis=-1)


def _heat_experiment(ds, m, mbrs, qb_hot, want_hot, payload, shards,
                     mesh, smoke) -> dict:
    """Heat-plan delta on the skewed stream: exchange messages under the
    count-balanced shard plan, after heat-aware co-location of the same
    server, and on a fresh ``placement="heat"`` server (co-location +
    hot-tile replicas).  Every answer must stay bit-identical to the
    dense reference — placement only moves bytes, never results."""
    ssrv = SpatialServer.from_method(
        m, mbrs, payload, ServeConfig(placement="sharded", shards=shards),
        mesh=mesh)
    counts, st0 = ssrv.range_counts(qb_hot)
    assert [int(c) for c in counts] == want_hot, (ds, m, "hot/balanced")
    for _ in range(4):      # accrue heat through the public batched path
        ssrv.range_counts(qb_hot)
    ssrv.rebalance()
    counts, st1 = ssrv.range_counts(qb_hot)
    assert [int(c) for c in counts] == want_hot, (ds, m, "hot/colocated")
    if ds == "osm":     # CI smoke gate: co-location must not add traffic
        assert st1["messages"] <= st0["messages"], \
            (m, st0["messages"], st1["messages"])

    top = 2 if smoke else 4
    hsrv = SpatialServer.from_method(
        m, mbrs, payload,
        ServeConfig(placement="heat", shards=shards,
                    policy=PlacementPolicy(heat_decay=0.9,
                                           replicate_top=top)),
        mesh=mesh)
    for _ in range(5):
        hsrv.range_counts(qb_hot)
    t0 = time.perf_counter()
    rep = hsrv.rebalance()
    dt_rb = time.perf_counter() - t0
    counts, st2 = hsrv.range_counts(qb_hot)
    assert [int(c) for c in counts] == want_hot, (ds, m, "hot/heat")
    emit(f"heat_plan/{ds}/{m}/d{shards}", dt_rb * 1e6,
         f"msgs_balanced={st0['messages']}"
         f";msgs_colocated={st1['messages']}"
         f";msgs_heat={st2['messages']}"
         f";replicated={rep['replicated_tiles']}"
         f";moved={rep['moved_tiles']}"
         f";imbalance={st0['probe_load_imbalance']:.2f}"
         f"->{st2['probe_load_imbalance']:.2f}"
         f";xbytes={st0['exchange_bytes']}->{st2['exchange_bytes']}")
    return dict(
        exchange_messages_hot_balanced=int(st0["messages"]),
        exchange_messages_hot_colocated=int(st1["messages"]),
        exchange_messages_hot_heat=int(st2["messages"]),
        exchange_bytes_hot=int(st0["exchange_bytes"]),
        exchange_bytes_hot_heat=int(st2["exchange_bytes"]),
        probe_load_imbalance_hot=round(
            float(st0["probe_load_imbalance"]), 3),
        probe_load_imbalance_hot_heat=round(
            float(st2["probe_load_imbalance"]), 3),
        heat_replicated_tiles=int(rep["replicated_tiles"]),
        heat_moved_tiles=int(rep["moved_tiles"]),
        heat_rebalance_ms=round(dt_rb * 1e3, 2))


def _interleaved_stream(ds: str, mbrs, qb, payload: int,
                        smoke: bool) -> dict:
    """Sustained append/delete/update/query churn against one server:
    ingest ops/sec and the query p50 while the alive mask and the
    compaction policy are doing real work."""
    rng = np.random.default_rng(0)
    n = int(mbrs.shape[0])
    head = mbrs[: 4 * n // 5]
    srv = SpatialServer.from_method(
        "bsp", head, payload,
        ServeConfig(slack=1024, compact_dead_frac=0.4))
    live = np.arange(head.shape[0])
    next_id = head.shape[0]
    rounds, m_app, m_del, m_upd = (4, 64, 32, 16) if smoke \
        else (12, 128, 64, 32)
    q_times = []

    def one_round():
        nonlocal live, next_id
        lo = rng.uniform(0.0, 1.0, (m_app, 2)).astype(np.float32)
        ex = rng.uniform(0.0, 0.01, (m_app, 2)).astype(np.float32)
        srv.append(np.concatenate([lo, lo + ex], axis=1))
        live = np.concatenate([live, np.arange(next_id, next_id + m_app)])
        next_id += m_app
        dels = rng.choice(live, m_del, replace=False)
        srv.delete(dels)
        live = np.setdiff1d(live, dels)
        upd = rng.choice(live, m_upd, replace=False)
        lo = rng.uniform(0.0, 1.0, (m_upd, 2)).astype(np.float32)
        ex = rng.uniform(0.0, 0.01, (m_upd, 2)).astype(np.float32)
        srv.update(upd, np.concatenate([lo, lo + ex], axis=1))
        tq = time.perf_counter()
        np.asarray(srv.range_counts(qb)[0])
        q_times.append(time.perf_counter() - tq)

    one_round()            # warmup: one scatter compile per size bucket
    q_times.clear()
    ops = rounds * (m_app + m_del + m_upd)
    t0 = time.perf_counter()
    for _ in range(rounds):
        one_round()
    total = time.perf_counter() - t0
    assert srv.stats["n"] == live.size
    p50_us = float(np.median(q_times) * 1e6)
    emit(f"interleaved_stream/{ds}/bsp", total * 1e6,
         f"ingest_ops_per_s={ops / max(total, 1e-9):.0f}"
         f";query_p50_us={p50_us:.1f}"
         f";compactions={srv.stats['compactions']}"
         f";restages={srv.stats['restages']};n_final={srv.stats['n']}")
    return dict(dataset=ds, layout="bsp", rounds=rounds,
                ingest_ops_per_s=round(ops / max(total, 1e-9), 1),
                query_p50_us=round(p50_us, 1),
                compactions=int(srv.stats["compactions"]),
                restages=int(srv.stats["restages"]),
                n_final=int(srv.stats["n"]))


def main(smoke: bool = False, json_out: bool = False) -> None:
    n, q, k, payload = (1200, 128, 4, 100) if smoke else (6000, 512, 8, 120)
    iters = 5 if smoke else 15      # range counts are cheap; drown drift
    if jax.device_count() > 1:
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()), ("d",))
        shards = jax.device_count()
    else:
        mesh, shards = None, 4          # exchange in vmap simulation
    rows, stream_rows = [], []
    for ds in DATASETS:
        mbrs = spatial_gen.dataset(ds, jax.random.PRNGKey(0), n)
        qb = _qboxes(jax.random.PRNGKey(1), q)
        pts = jax.random.uniform(jax.random.PRNGKey(2), (q, 2))
        ref = range_mod.range_query_ref(np.asarray(mbrs), np.asarray(qb))
        want = [len(r) for r in ref]
        qb_hot = _hot_qboxes(jax.random.PRNGKey(3), q)
        ref_hot = range_mod.range_query_ref(np.asarray(mbrs),
                                            np.asarray(qb_hot))
        want_hot = [len(r) for r in ref_hot]
        for m in METHODS:
            srv = SpatialServer.from_method(m, mbrs, payload, mesh=mesh)
            usrv = SpatialServer.from_method(
                m, mbrs, payload, ServeConfig(local_index="off"),
                mesh=mesh)
            ssrv = SpatialServer.from_method(
                m, mbrs, payload,
                ServeConfig(placement="sharded", shards=shards),
                mesh=mesh)
            hsrv = SpatialServer.from_method(
                m, mbrs, payload, ServeConfig(local_index="hilbert"),
                mesh=mesh)
            counts, rstats = srv.range_counts(qb)
            assert [int(c) for c in counts] == want, (ds, m, "local")
            ucounts, _ = usrv.range_counts(qb)
            assert [int(c) for c in ucounts] == want, (ds, m, "unindexed")
            dcounts, _ = srv.range_counts(qb, pruned=False)
            assert [int(c) for c in dcounts] == want, (ds, m, "dense")
            scounts, sstats = ssrv.range_counts(qb)
            assert [int(c) for c in scounts] == want, (ds, m, "sharded")
            hcounts, _ = hsrv.range_counts(qb)
            assert [int(c) for c in hcounts] == want, (ds, m, "hilbert")
            skip_rate = srv.chunk_skip_rate(qb)
            skip_rate_h = hsrv.chunk_skip_rate(qb)

            # streaming: stage 90% with slack, stream the tail in, then
            # force one tile overflow and time the re-stage
            head, tail = mbrs[: 9 * n // 10], np.asarray(mbrs[9 * n // 10:])
            asrv = SpatialServer.from_method(m, head, payload,
                                             ServeConfig(slack=512))
            bs = max(64, tail.shape[0] // 8)
            # warmup on a throwaway server: the eager scatter steps are
            # cached by shape globally, and identical batches produce
            # identical size buckets — the timed loop below runs warm
            wsrv = SpatialServer.from_method(m, head, payload,
                                             ServeConfig(slack=512))
            for i in range(0, tail.shape[0], bs):
                wsrv.append(tail[i:i + bs])
            del wsrv
            append_bytes, append_rates = 0, []
            t0 = time.perf_counter()
            for i in range(0, tail.shape[0], bs):
                chunk = tail[i:i + bs]
                tb0 = time.perf_counter()
                rep = asrv.append(chunk)
                append_rates.append(
                    chunk.shape[0] / max(time.perf_counter() - tb0, 1e-9))
                append_bytes += rep["bytes_transferred"]
            dt_append = time.perf_counter() - t0
            append_rate = float(np.median(append_rates))
            acounts, _ = asrv.range_counts(qb)
            assert [int(c) for c in acounts] == want, (ds, m, "append")
            append_restages = asrv.stats["restages"]
            # cap+1 copies into one tile guarantees the overflow path
            tb = np.asarray(asrv.parts.boxes)[0]
            ctr = [(tb[0] + tb[2]) / 2, (tb[1] + tb[3]) / 2]
            burst = np.tile(np.asarray(ctr + ctr, np.float32),
                            (asrv.stats["cap"] + 1, 1))
            t0 = time.perf_counter()
            rep = asrv.append(burst)
            dt_restage = time.perf_counter() - t0
            assert rep["restaged"], (ds, m, "restage")

            # interleaved: the local-vs-unindexed and pruned-vs-sharded
            # deltas are the point, so machine drift must hit all legs
            # equally
            us_p, us_u, us_d, us_s = timeit_many(
                [lambda: srv.range_counts(qb)[0],
                 lambda: usrv.range_counts(qb)[0],
                 lambda: srv.range_counts(qb, pruned=False)[0],
                 lambda: ssrv.range_counts(qb)[0]],
                warmup=1, iters=iters)
            emit(f"range_serve/{ds}/{m}/q{q}", us_p,
                 f"qps={q / (us_p * 1e-6):.0f}"
                 f";fanout={rstats['fanout_mean']:.2f}"
                 f";f_max={rstats['f_max']};tiles={srv.stats['t']}"
                 f";chunks={srv.stats['chunks']}"
                 f";chunk_skip={skip_rate:.3f}"
                 f";chunk_skip_hilbert={skip_rate_h:.3f}"
                 f";unindexed_us={us_u:.1f}"
                 f";dense_us={us_d:.1f};speedup={us_d / us_p:.2f}")
            emit(f"range_serve_sharded/{ds}/{m}/q{q}/d{shards}", us_s,
                 f"qps={q / (us_s * 1e-6):.0f}"
                 f";msgs={sstats['messages']};f_local={sstats['f_local']}"
                 f";xbytes={sstats['exchange_bytes']}"
                 f";imbalance={sstats['probe_load_imbalance']:.2f}"
                 f";dev_bytes={ssrv.resident_tile_bytes()}"
                 f";repl_bytes={srv.resident_tile_bytes()}"
                 f";mem_ratio={srv.resident_tile_bytes() / max(ssrv.resident_tile_bytes(), 1):.2f}")

            _, _, _, kstats = srv.knn(pts, k)
            us_pk = timeit(lambda: srv.knn(pts, k)[0], warmup=1, iters=3)
            us_dk = timeit(lambda: srv.knn(pts, k, pruned=False)[0],
                           warmup=1, iters=3)
            us_sk = timeit(lambda: ssrv.knn(pts, k)[0], warmup=1, iters=3)
            emit(f"append_serve/{ds}/{m}", dt_append * 1e6,
                 f"objs_per_s={append_rate:.0f}"
                 f";bytes_per_obj={append_bytes / tail.shape[0]:.1f}"
                 f";restages={append_restages}"
                 f";restage_ms={dt_restage * 1e3:.1f}")
            emit(f"knn_serve/{ds}/{m}/k{k}", us_pk,
                 f"qps={q / (us_pk * 1e-6):.0f}"
                 f";fanout={kstats['fanout_mean']:.2f}"
                 f";f_max={kstats['f_max']};rounds={kstats['rounds']}"
                 f";dense_us={us_dk:.1f};speedup={us_dk / us_pk:.2f}"
                 f";sharded_us={us_sk:.1f}")
            rows.append(dict(
                dataset=ds, layout=m, queries=q,
                range_qps=round(q / (us_p * 1e-6), 1),
                range_qps_unindexed=round(q / (us_u * 1e-6), 1),
                range_qps_dense=round(q / (us_d * 1e-6), 1),
                range_qps_sharded=round(q / (us_s * 1e-6), 1),
                knn_qps=round(q / (us_pk * 1e-6), 1),
                knn_qps_dense=round(q / (us_dk * 1e-6), 1),
                fanout_mean=round(rstats["fanout_mean"], 3),
                f_max=int(rstats["f_max"]),
                knn_rounds=int(kstats["rounds"]),
                tiles=int(srv.stats["t"]), chunks=int(srv.stats["chunks"]),
                chunk_skip_rate=round(skip_rate, 4),
                chunk_skip_rate_hilbert=round(skip_rate_h, 4),
                append_objs_per_s=round(append_rate, 1),
                append_bytes_per_obj=round(
                    append_bytes / tail.shape[0], 1),
                append_restages=int(append_restages),
                restage_ms=round(dt_restage * 1e3, 2),
                exchange_messages=int(sstats["messages"]),
                exchange_bytes=int(sstats["exchange_bytes"]),
                probe_load_imbalance=round(
                    float(sstats["probe_load_imbalance"]), 3),
                shard_bytes_per_device=int(ssrv.resident_tile_bytes()),
            ))
            rows[-1].update(_heat_experiment(
                ds, m, mbrs, qb_hot, want_hot, payload, shards, mesh,
                smoke))
        stream_rows.append(_interleaved_stream(ds, mbrs, qb, payload, smoke))
    if json_out:
        # aggregate the local-vs-unindexed comparison per dataset: the
        # per-layout ratios carry ±5% machine noise even interleaved,
        # the geomean is the stable "no worse than unindexed" signal
        summary = {}
        for ds in DATASETS:
            ratios = [r["range_qps"] / r["range_qps_unindexed"]
                      for r in rows if r["dataset"] == ds]
            prod = 1.0
            for x in ratios:
                prod *= x
            summary[f"{ds}_range_local_over_unindexed_geomean"] = round(
                prod ** (1.0 / len(ratios)), 4)
            summary[f"{ds}_chunk_skip_rate_mean"] = round(
                sum(r["chunk_skip_rate"] for r in rows
                    if r["dataset"] == ds) / len(ratios), 4)
            summary[f"{ds}_chunk_skip_rate_hilbert_mean"] = round(
                sum(r["chunk_skip_rate_hilbert"] for r in rows
                    if r["dataset"] == ds) / len(ratios), 4)
            # geomean exchange-message cut of the heat plan vs the
            # count-balanced shard plan on the skewed hotspot stream —
            # the headline number for query-heat-aware placement
            hratios = [r["exchange_messages_hot_balanced"]
                       / max(r["exchange_messages_hot_heat"], 1)
                       for r in rows if r["dataset"] == ds]
            hprod = 1.0
            for x in hratios:
                hprod *= x
            hgeo = hprod ** (1.0 / len(hratios))
            summary[f"{ds}_heat_exchange_messages_cut_geomean"] = round(
                1.0 - 1.0 / hgeo, 4)
        payload_doc = dict(
            bench="serving", smoke=smoke, n_objects=n, batch_queries=q,
            knn_k=k, payload=payload, backend=jax.default_backend(),
            devices=jax.device_count(), shards=shards, summary=summary,
            rows=rows, interleaved_stream=stream_rows)
        JSON_PATH.write_text(json.dumps(payload_doc, indent=2) + "\n")
        print(f"# wrote {JSON_PATH}", file=sys.stderr)


if __name__ == "__main__":
    use_compile_cache(str(pathlib.Path(__file__).resolve().parents[1]))
    main(smoke="--smoke" in sys.argv, json_out="--json" in sys.argv)
