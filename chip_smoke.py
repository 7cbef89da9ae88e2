"""Bring-up smoke run of the spatial serving stack on a TPU.

Drives the main path once through the entry points a user calls — a
``SpatialServer`` staged at a real data size with ``ServeFrontend`` in
front of it, plus the spatial-join engine — and checks every answer
against the repository's numpy brute-force oracles and the kernels'
``ref`` twins.

    python chip_smoke.py             # one chip: phases 1-6 below
    python chip_smoke.py --chips 4   # four chips: the sharded placement

One chip:
  1. device   JAX must report a TPU; anything else exits non-zero.
  2. staging  1,000,000 ``osm`` objects partitioned with ``bsp`` at
              payload 4000, default ``ServeConfig`` (replicated, pruned,
              ``local_index="x"``).
  3. serving  range_counts, range_ids and kNN (k=10) requests through
              ``ServeFrontend``, sent twice (cold, then steady); a
              seeded subset is checked against the numpy oracles.
  4. ingest   a second server with append slack: one append batch and
              a batch of deletes, then the same checks on the live set.
  5. join     ``plan_join("bsp")`` + ``spatial_join_count`` on
              100k x 100k ``osm`` against the ``mbr_join`` ref on the
              same plan and an all-pairs ref count.
  6. kernel   the compiled serving probe holds the Pallas kernel
              (``tpu_custom_call``).

Four chips (``--chips 4``): the same dataset staged with
``placement="sharded"`` on a 4-device mesh answers the same requests
bit-identically to a one-device replicated server and to the oracle,
with its tiles spread over all four devices.

Each phase prints its wall seconds, the seconds JAX spent tracing and
compiling inside it, and the persistent-cache hits.  The last line of
stdout is one JSON object naming the device.  Any failed check raises,
so the script exits non-zero.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

N_OBJECTS, PAYLOAD, SEED = 1_000_000, 4000, 0
N_QUERIES, N_CHECK, K = 256, 64, 10
# a flagged kNN answer is re-requested at the next rung (default 1024
# first); the last rung exceeds any candidate count f_max 16 can reach
KNN_MAX_CAND = (16_384, 262_144)
N_APPEND, N_DELETE, SLACK = 10_000, 5_000, 256
N_JOIN = 100_000


def require(ok, message) -> None:
    """A failed check raises (under ``python -O`` too, unlike ``assert``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {message}")


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and the
    persistent-cache hits, read from ``jax.monitoring`` events."""

    def __init__(self, jax):
        self.secs, self.hits = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event.startswith("/jax/core/compile/"):
            self.secs += duration_secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


class Phase:
    """Context manager printing one phase's wall and compile seconds."""

    def __init__(self, clock: CompileClock, name: str):
        self.clock, self.name = clock, name

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0, self.h0 = self.clock.secs, self.clock.hits
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            wall = time.perf_counter() - self.t0
            comp = self.clock.secs - self.c0
            print(f"[{self.name}] wall_s={wall:.3f} compile_s={comp:.3f} "
                  f"cache_hits={self.clock.hits - self.h0}", flush=True)


def require_tpu(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform "
                 f"{devs[0].platform!r}); this script runs on the chip only")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX sees {len(devs)}")
    print(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)}", flush=True)
    return devs


# --------------------------------------------------------------------------
# workload and oracle checks
# --------------------------------------------------------------------------

def make_requests(np, mbrs_np, parts, seed: int, n: int):
    """Range boxes at the local zoom level (a fraction of the side of
    the partition containing a random object's centre) and kNN points
    at random object centres."""
    rng = np.random.default_rng(seed)
    centres = (mbrs_np[:, :2] + mbrs_np[:, 2:]) * 0.5
    c = centres[rng.choice(len(centres), n, replace=False)]
    boxes = np.asarray(parts.boxes)[np.asarray(parts.valid)]
    inside = ((boxes[None, :, 0] <= c[:, None, 0])
              & (c[:, None, 0] <= boxes[None, :, 2])
              & (boxes[None, :, 1] <= c[:, None, 1])
              & (c[:, None, 1] <= boxes[None, :, 3]))
    home = boxes[np.argmax(inside, axis=1)]
    side = np.minimum(home[:, 2] - home[:, 0], home[:, 3] - home[:, 1])
    half = side * rng.uniform(0.02, 0.3, n)
    qboxes = np.concatenate([c - half[:, None], c + half[:, None]], axis=1)
    pts = centres[rng.choice(len(centres), n, replace=False)]
    return qboxes.astype(np.float32), pts.astype(np.float32)


async def _serve(fe, qboxes, pts, k: int):
    """All requests at the default ``max_cand``; then, as the kNN
    contract asks of a client, each flagged (overflowed) kNN request is
    sent again at the next ``KNN_MAX_CAND`` rung.  -> (counts, ids,
    knn) responses and the number re-sent at each rung."""
    counts, ids, knn = await asyncio.gather(
        asyncio.gather(*(fe.range_counts(b) for b in qboxes)),
        asyncio.gather(*(fe.range_ids(b) for b in qboxes)),
        asyncio.gather(*(fe.knn(p, k) for p in pts)))
    resent = []
    for max_cand in KNN_MAX_CAND:
        again = [i for i, r in enumerate(knn) if r.value and r.value[2]]
        if not again:
            break
        resent.append(len(again))
        redo = await asyncio.gather(
            *(fe.knn(pts[i], k, max_cand=max_cand) for i in again))
        for i, r in zip(again, redo):
            knn[i] = r
    return (counts, ids, knn), resent


def serve(server, qboxes, pts, k: int):
    """Send every request through a fresh ``ServeFrontend`` and await
    it; every response must be OK.  -> (counts, ids, knn) value lists."""
    from repro.serve.frontend import Outcome, ServeFrontend

    async def main():
        async with ServeFrontend(server) as fe:
            return await _serve(fe, qboxes, pts, k)

    out, resent = asyncio.run(main())
    for kind, resps in zip(("range_counts", "range_ids", "knn"), out):
        bad = [r.outcome for r in resps if r.outcome != Outcome.OK]
        require(not bad, f"{kind}: {len(bad)} responses not OK: {bad[:3]}")
    print(f"  knn re-sent after overflow at max_cand "
          f"{list(KNN_MAX_CAND[:len(resent)])}: {resent}", flush=True)
    return tuple([r.value for r in resps] for resps in out)


def same_answers(np, a, b) -> bool:
    counts_a, ids_a, knn_a = a
    counts_b, ids_b, knn_b = b
    return (counts_a == counts_b
            and all(np.array_equal(x[0], y[0]) and x[1:] == y[1:]
                    for x, y in zip(ids_a, ids_b))
            and all(np.array_equal(x[0], y[0]) and np.array_equal(x[1], y[1])
                    and x[2] == y[2] for x, y in zip(knn_a, knn_b)))


def check_oracle(np, answers, mbrs_np, qboxes, pts, k: int, seed: int,
                 n_check: int, label: str):
    """Compare a seeded subset with the numpy oracles: counts and ids
    exactly, kNN distances to f32 rounding (``mbrs_np`` rows of dead
    objects are sentinel boxes)."""
    from repro.query.knn import knn_ref
    from repro.query.range import range_query_ref

    counts, ids, knn = answers
    for i, (c, (hid, hc, hov)) in enumerate(zip(counts, ids)):
        require(c == hc, f"{label}: query {i} range_counts {c} != ids {hc}")
    sub = np.random.default_rng(seed + 7).choice(len(qboxes), n_check,
                                                 replace=False)
    want = range_query_ref(mbrs_np, qboxes[sub])
    for i, w in zip(sub, want):
        hid, hc, hov = ids[i]
        mh = hid.shape[0]
        require(counts[i] == len(w),
                f"{label}: query {i} count {counts[i]} != oracle {len(w)}")
        got = hid[hid >= 0]
        require(np.array_equal(got, w[:mh]) and hov == (len(w) > mh),
                f"{label}: query {i} ids differ from the oracle")
    for lo in range(0, n_check, 8):
        rows = sub[lo:lo + 8]
        want_ids, want_d2 = knn_ref(mbrs_np, pts[rows], k)
        for j, i in enumerate(rows):
            nn_ids, nn_d2, ovf = knn[i]
            require(not ovf, f"{label}: knn query {i} flagged overflow")
            require(np.array_equal(nn_ids, want_ids[j]),
                    f"{label}: knn query {i} ids {nn_ids} != {want_ids[j]}")
            # ids exact; distances as the repository's tests compare
            # them (the device may contract the f32 multiply-add)
            require(np.allclose(nn_d2, want_d2[j], rtol=1e-5, atol=1e-7),
                    f"{label}: knn query {i} d2 {nn_d2} != {want_d2[j]}")
    hits = np.asarray(counts)
    print(f"  {label}: {len(counts)} range_counts + {len(ids)} range_ids + "
          f"{len(knn)} knn(k={k}) answers OK; {n_check} checked against "
          f"the oracle exactly; hits/query mean={hits.mean():.1f} "
          f"max={hits.max()}", flush=True)


def stage(jax, np, name, clock, mbrs, config=None, mesh=None, parts=None):
    """Partition ``mbrs`` with ``bsp`` (or reuse ``parts``) and stage."""
    from repro.serve import SpatialServer
    with Phase(clock, name):
        if parts is None:
            server = SpatialServer.from_method("bsp", mbrs, PAYLOAD, config,
                                               mesh=mesh)
        else:
            server = SpatialServer(parts, mbrs, config, mesh=mesh,
                                   method="bsp")
        jax.block_until_ready(jax.tree.leaves(
            server.layout if server.layout is not None
            else server.slayout.canon_shards))
        st = server.stats
        print(f"  n={st['n']} T={st['t']} t_live={st['t_live']} "
              f"cap={st['cap']} chunks={st['chunks']} "
              f"placement={server.config.placement}", flush=True)
    return server


def serve_phase(np, name, clock, server, mbrs_np, qboxes, pts):
    """Cold pass (compiles included), then a steady pass with the same
    requests that must answer identically."""
    with Phase(clock, f"{name} cold"):
        cold = serve(server, qboxes, pts, K)
    with Phase(clock, f"{name} steady"):
        steady = serve(server, qboxes, pts, K)
    require(same_answers(np, cold, steady), f"{name}: steady != cold answers")
    check_oracle(np, cold, mbrs_np, qboxes, pts, K, SEED, N_CHECK, name)
    return cold


# --------------------------------------------------------------------------
# one chip
# --------------------------------------------------------------------------

def one_chip(jax, np, clock, n_objects: int = N_OBJECTS,
             n_join: int = N_JOIN):
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.data import spatial_gen
    from repro.kernels.mbr_join import ref as mref
    from repro.kernels.range_probe import ops as rops
    from repro.query import engine, join as join_mod
    from repro.query import range as range_mod
    from repro.serve import ServeConfig, router

    mbrs = spatial_gen.dataset("osm", jax.random.PRNGKey(SEED), n_objects)
    mbrs_np = np.asarray(mbrs)
    server = stage(jax, np, "staging", clock, mbrs)
    qboxes, pts = make_requests(np, mbrs_np, server.parts, SEED, N_QUERIES)
    serve_phase(np, "serving", clock, server, mbrs_np, qboxes, pts)

    # ingest: slack slots absorb an append of jittered copies (same
    # spatial distribution), then tombstone a random id sample
    srv2 = stage(jax, np, "ingest staging", clock, mbrs,
                 ServeConfig(slack=SLACK))
    rng = np.random.default_rng(SEED + 1)
    src = mbrs_np[rng.choice(n_objects, N_APPEND, replace=False)]
    shift = rng.normal(0.0, 1e-4, (N_APPEND, 2)).astype(np.float32)
    new = src + np.concatenate([shift, shift], axis=1)
    dead = rng.choice(n_objects + N_APPEND, N_DELETE, replace=False)
    with Phase(clock, "ingest append+delete"):
        rep_a = srv2.append(new)
        rep_d = srv2.delete(dead)
        print(f"  appended={rep_a['appended']} restaged={rep_a['restaged']} "
              f"deleted={rep_d['deleted']} n={rep_d['n']}", flush=True)
    live_np = np.concatenate([mbrs_np, new]).astype(np.float32)
    live_np[dead] = np.asarray([9e9, 9e9, -9e9, -9e9], np.float32)
    serve_phase(np, "ingest serving", clock, srv2, live_np, qboxes, pts)
    del srv2

    # join: the offline Algorithm-1 engine on one device
    r = spatial_gen.dataset("osm", jax.random.PRNGKey(SEED + 2), n_join)
    s = spatial_gen.dataset("osm", jax.random.PRNGKey(SEED + 3), n_join)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("d",))
    with Phase(clock, "join"):
        plan = engine.plan_join("bsp", r, s, PAYLOAD, 1)
        got = engine.spatial_join_count(plan, mesh, "d")
        print(f"  tiles={plan.stats['k']} cap_r={plan.stats['cap_r']} "
              f"cap_s={plan.stats['cap_s']} pairs={got}", flush=True)
    with Phase(clock, "join ref"):
        uni = jnp.asarray(plan.universe)

        def tile_ref(a):
            rt, st, tb = a
            hit = mref.intersect_mask(rt, st) & join_mod.rp_own_mask(
                rt, st, tb, uni)
            return jnp.sum(hit.astype(jnp.int32))

        want_plan = int(jnp.sum(jax.jit(lambda *a: jax.lax.map(tile_ref, a))(
            jnp.asarray(plan.r_tiles[0]), jnp.asarray(plan.s_tiles[0]),
            jnp.asarray(plan.tile_boxes[0]))))
        want_all = int(jnp.sum(jax.lax.map(
            lambda rc: mref.intersect_count(rc, s),
            r.reshape(-1, 1000, 4))))
    require(got == want_plan == want_all, (got, want_plan, want_all))
    print(f"  join count {got} == ref on the plan == all-pairs ref",
          flush=True)

    # kernel: the serving probe compiles to the Pallas kernel
    with Phase(clock, "kernel check"):
        lay = server.layout
        qb = jnp.asarray(np.resize(qboxes, (512, 4)))
        cand, _, _ = router.candidates_from_overlap(
            router.probe_overlap(lay.probe_boxes, qb), 16)
        texts = {
            "gathered_counts_skip": jax.jit(rops.gathered_counts_skip).lower(
                qb, lay.canon_tiles, lay.chunk_boxes, cand,
                alive=lay.alive).compile().as_text(),
            "pruned_range_counts": range_mod.pruned_range_counts.lower(
                qb, lay.canon_tiles, cand, lay.chunk_boxes,
                lay.alive).compile().as_text(),
        }
        for fn, text in texts.items():
            n_calls = text.count("tpu_custom_call")
            require(n_calls > 0,
                    f"{fn}: no Pallas kernel in the compiled step")
            print(f"  {fn} Q=512 F=16 cap={lay.canon_tiles.shape[1]} "
                  f"C={lay.chunk_boxes.shape[1]}: tpu_custom_call x{n_calls}",
                  flush=True)


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------

def four_chips(jax, np, clock, devices, n_objects: int = N_OBJECTS):
    from jax.sharding import Mesh

    from repro.data import spatial_gen
    from repro.serve import ServeConfig

    mbrs = spatial_gen.dataset("osm", jax.random.PRNGKey(SEED), n_objects)
    mbrs_np = np.asarray(mbrs)
    mesh = Mesh(np.asarray(devices[:4]), ("d",))
    sharded = stage(jax, np, "sharded staging", clock, mbrs,
                    ServeConfig(placement="sharded"), mesh=mesh)
    s = sharded.slayout
    t = sharded.stats["t"]
    per_dev = math.ceil(t / 4)
    for name in ("canon_shards", "id_shards", "alive_shards"):
        arr = getattr(s, name)
        require(len(arr.sharding.device_set) == 4,
                f"{name} spans {len(arr.sharding.device_set)} devices, not 4")
        for shard in arr.addressable_shards:
            require(shard.data.shape[1] <= per_dev,
                    f"{name}: {shard.device} holds {shard.data.shape[1]} rows")
    owned = np.bincount(s.owner, minlength=4)
    require(owned.max() <= per_dev, f"tiles per owner {owned} > {per_dev}")
    print(f"  tiles per device {owned.tolist()} (ceil(T/4)={per_dev}), "
          f"shard rows {s.canon_shards.shape[1]}", flush=True)

    qboxes, pts = make_requests(np, mbrs_np, sharded.parts, SEED, N_QUERIES)
    got = serve_phase(np, "sharded serving", clock, sharded, mbrs_np,
                      qboxes, pts)
    # the same partitioning, replicated on one device
    replicated = stage(jax, np, "replicated staging", clock, mbrs,
                       parts=sharded.parts)
    with Phase(clock, "replicated serving"):
        want = serve(replicated, qboxes, pts, K)
    require(same_answers(np, got, want), "sharded != replicated answers")
    print("  sharded answers == one-device replicated answers", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        sys.exit(f"chip_smoke: {ROOT} holds no src/repro; run this script "
                 f"from a checkout of the repository")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core.compat import use_compile_cache
    print(f"compile cache: {use_compile_cache(ROOT)}", flush=True)

    import jax
    import numpy as np

    devices = require_tpu(jax, args.chips)
    clock = CompileClock(jax)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(jax, np, clock, devices)
    else:
        one_chip(jax, np, clock)
    print(f"total wall_s={time.perf_counter() - t0:.3f} "
          f"compile_s={clock.secs:.3f} cache_hits={clock.hits}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
