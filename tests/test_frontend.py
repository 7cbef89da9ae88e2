"""The async request plane, tested deterministically.

Policy (admission, DRR fairness, deadline-or-full closing, the batch
shape ladder, timeouts) runs on a ``VirtualClock`` — no sleeps, no
wall-clock flakiness.  Exactness is the usual bar: padded front-end
batches must return answers **bit-identical** to calling the batched
``SpatialServer`` API directly with the same queries, on both
placements (and on a real 8-device mesh in the CI virtual-device job).
The asyncio wrapper gets a live smoke test; everything timing-critical
stays on the virtual clock.
"""
import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import spatial_gen
from repro.serve import ServeConfig, SpatialServer
from repro.serve.frontend import (
    Arrival,
    FrontendConfig,
    Outcome,
    Request,
    RequestPlane,
    ServeFrontend,
    VirtualClock,
    execute_batch,
    poisson_workload,
    simulate_open_loop,
)
from repro.serve.frontend.plane import Batch

N, PAYLOAD = 1500, 130


def _req(kind="range_counts", payload=None, params=(), tenant="default",
         deadline=float("inf")):
    return Request(kind=kind,
                   payload=payload if payload is not None else np.zeros(4),
                   params=params, tenant=tenant, deadline=deadline)


@pytest.fixture(scope="module")
def mbrs():
    return spatial_gen.dataset("osm", jax.random.PRNGKey(0), N)


@pytest.fixture(scope="module")
def qboxes():
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    c = jax.random.uniform(k1, (13, 2))
    s = jax.random.uniform(k2, (13, 2)) * 0.06
    return np.asarray(jnp.concatenate([c - s, c + s], axis=-1))


@pytest.fixture(scope="module")
def pts():
    return np.asarray(jax.random.uniform(jax.random.PRNGKey(2), (13, 2)))


@pytest.fixture(scope="module", params=["replicated", "sharded"])
def server(request, mbrs):
    cfg = (ServeConfig() if request.param == "replicated"
           else ServeConfig(placement="sharded", shards=4))
    return SpatialServer.from_method("bsp", mbrs, PAYLOAD, cfg)


# -- config -----------------------------------------------------------------

def test_config_validates():
    cfg = FrontendConfig()
    assert cfg.max_batch == cfg.ladder[-1]
    assert cfg.width_for(1) == cfg.ladder[0]
    assert cfg.width_for(cfg.ladder[-1]) == cfg.ladder[-1]
    assert cfg.replace(max_delay=0.5).max_delay == 0.5
    for bad in (dict(ladder=()), dict(ladder=(128, 64)),
                dict(ladder=(0, 64)), dict(max_delay=-1.0),
                dict(queue_limit=0), dict(quantum=0)):
        with pytest.raises(ValueError):
            FrontendConfig(**bad)
    with pytest.raises(ValueError):
        FrontendConfig(ladder=(4,)).width_for(5)


# -- batch forming: deadline-or-full on a virtual clock ---------------------

def test_batch_closes_on_deadline_not_before():
    cfg = FrontendConfig(ladder=(4, 8), max_delay=0.010)
    plane = RequestPlane(cfg)
    for t in (0.0, 0.001, 0.002):
        assert plane.submit(_req(), now=t)
    assert plane.next_due(0.002) == pytest.approx(0.010)
    batch, expired = plane.form_batch(0.009)
    assert batch is None and not expired          # oldest not yet due
    batch, expired = plane.form_batch(0.010)      # exactly due closes
    assert batch is not None and not expired
    assert len(batch.requests) == 3 and batch.width == 4
    assert [r.seq for r in batch.requests] == [0, 1, 2]   # FIFO
    assert plane.pending == 0


def test_batch_closes_immediately_when_full():
    cfg = FrontendConfig(ladder=(4, 8), max_delay=10.0)
    plane = RequestPlane(cfg)
    for _ in range(9):
        plane.submit(_req(), now=0.0)
    assert plane.next_due(0.0) == 0.0             # full: due now
    batch, _ = plane.form_batch(0.0)
    assert len(batch.requests) == 8 and batch.width == 8
    assert plane.pending == 1                      # remainder waits
    batch, _ = plane.form_batch(10.0)
    assert len(batch.requests) == 1 and batch.width == 4


def test_ladder_pads_to_smallest_fitting_rung():
    cfg = FrontendConfig(ladder=(4, 8, 16), max_delay=0.0)
    plane = RequestPlane(cfg)
    for n, want in ((3, 4), (5, 8), (9, 16)):
        for _ in range(n):
            plane.submit(_req(), now=0.0)
        batch, _ = plane.form_batch(0.0)
        assert len(batch.requests) == n and batch.width == want


def test_kinds_and_params_batch_separately():
    plane = RequestPlane(FrontendConfig(max_delay=0.0))
    plane.submit(_req("range_ids", params=(64,)), now=0.0)
    plane.submit(_req("range_ids", params=(128,)), now=0.0)
    plane.submit(_req("knn", np.zeros(2), (4, 64)), now=0.0)
    widths = set()
    for _ in range(3):
        batch, _ = plane.form_batch(0.0)
        assert len(batch.requests) == 1
        widths.add((batch.kind, batch.params))
    assert widths == {("range_ids", (64,)), ("range_ids", (128,)),
                      ("knn", (4, 64))}
    assert plane.form_batch(0.0) == (None, [])
    with pytest.raises(ValueError):
        plane.submit(_req("nearest"), now=0.0)


# -- fairness: deficit round robin across tenants ---------------------------

def test_drr_hot_tenant_cannot_starve_others():
    cfg = FrontendConfig(ladder=(8,), max_delay=0.0, quantum=2)
    plane = RequestPlane(cfg)
    for i in range(100):
        plane.submit(_req(tenant="hog"), now=0.0)
    for i in range(4):
        plane.submit(_req(tenant=f"small{i}"), now=0.0)
    batch, _ = plane.form_batch(0.0)
    by_tenant = {}
    for r in batch.requests:
        by_tenant[r.tenant] = by_tenant.get(r.tenant, 0) + 1
    # one 8-slot batch: hog gets its 2-request quantum turns, every
    # small tenant gets served in the same batch
    assert by_tenant == {"hog": 4, "small0": 1, "small1": 1,
                         "small2": 1, "small3": 1}


def test_deadline_close_serves_exhausted_deficit_tenant():
    """The DRR × deadline interaction: a class queue that is not full
    must still close at ``max_delay`` even when the hot tenant's
    rotation turns would exhaust its quantum many times over — and the
    starved tenant's request rides the same deadline-formed batch
    (fairness never delays a due close)."""
    cfg = FrontendConfig(ladder=(8,), max_delay=0.010, quantum=2)
    plane = RequestPlane(cfg)
    for _ in range(6):
        plane.submit(_req(tenant="hog"), now=0.0)
    plane.submit(_req(tenant="slow"), now=0.002)
    # 7 < max_batch: nothing closes before the oldest's max_delay
    assert plane.form_batch(0.009) == (None, [])
    assert plane.next_due(0.009) == pytest.approx(0.010)
    batch, expired = plane.form_batch(0.010)
    assert batch is not None and not expired
    assert len(batch.requests) == 7
    # the pop order shows the deficit turns: hog's 2-request quantum,
    # then slow's turn, then hog drains through repeat rotation visits
    assert [r.tenant for r in batch.requests] == \
        ["hog", "hog", "slow", "hog", "hog", "hog", "hog"]
    assert plane.pending == 0


def test_deadline_expiry_inside_exhausted_deficit_batch():
    """A starved tenant's request whose own deadline lapses while hog
    turns consumed earlier batches is timed out at pop time — counted,
    returned separately, never executed — and the deadline-formed
    batch still carries the live requests."""
    cfg = FrontendConfig(ladder=(4,), max_delay=0.010, quantum=4)
    plane = RequestPlane(cfg)
    for _ in range(4):
        plane.submit(_req(tenant="hog"), now=0.0)
    doomed = _req(tenant="slow", deadline=0.004)
    plane.submit(doomed, now=0.0)
    batch, expired = plane.form_batch(0.0)   # full: hog's quantum fills
    assert [r.tenant for r in batch.requests] == ["hog"] * 4
    assert not expired
    # slow's lone request is now overdue for the class deadline but
    # past its own: the close still happens, the request times out
    assert plane.next_due(0.009) == pytest.approx(0.010)
    batch, expired = plane.form_batch(0.010)
    assert batch is None and expired == [doomed]
    assert plane.metrics.timed_out == 1
    assert plane.pending == 0


def test_drr_rotation_persists_across_batches():
    cfg = FrontendConfig(ladder=(2,), max_delay=0.0, quantum=1)
    plane = RequestPlane(cfg)
    for t in ("a", "b", "c"):
        for _ in range(2):
            plane.submit(_req(tenant=t), now=0.0)
    order = []
    for _ in range(3):
        batch, _ = plane.form_batch(0.0)
        order.append([r.tenant for r in batch.requests])
    # round robin continues where the last batch stopped, so every
    # tenant is fully served after 3 batches of 2
    assert sorted(t for pair in order for t in pair) == list("aabbcc")
    assert order[0] == ["a", "b"] and order[1] == ["c", "a"]


# -- admission control and deadlines ----------------------------------------

def test_backpressure_rejects_at_queue_limit():
    plane = RequestPlane(FrontendConfig(queue_limit=3))
    assert all(plane.submit(_req(tenant="t"), 0.0) for _ in range(3))
    assert not plane.submit(_req(tenant="t"), 0.0)
    m = plane.metrics
    assert m.rejected == 1 and m.admitted == 3
    assert m.tenants["t"].rejected == 1
    # draining the queue re-opens admission
    plane.form_batch(1.0)
    assert plane.submit(_req(tenant="t"), 1.0)


def test_expired_requests_time_out_not_execute():
    plane = RequestPlane(FrontendConfig(ladder=(4,), max_delay=0.0))
    dead = _req(deadline=0.5)
    live = _req(deadline=5.0)
    plane.submit(dead, 0.0)
    plane.submit(live, 0.0)
    batch, expired = plane.form_batch(1.0)
    assert expired == [dead]
    assert batch.requests == [live]
    assert plane.metrics.timed_out == 1


def test_default_deadline_budget_applies():
    plane = RequestPlane(FrontendConfig(default_deadline=0.25))
    r = _req()
    plane.submit(r, 1.0)
    assert r.deadline == pytest.approx(1.25)
    explicit = _req(deadline=9.0)
    plane.submit(explicit, 1.0)
    assert explicit.deadline == 9.0               # explicit wins


# -- metrics ----------------------------------------------------------------

def test_metrics_fill_ratio_and_padded_slots():
    plane = RequestPlane(FrontendConfig(ladder=(8,), max_delay=0.0))
    for _ in range(5):
        plane.submit(_req(), 0.0)
    plane.form_batch(0.0)
    m = plane.metrics
    assert m.batch_slots == 8 and m.batch_fill == 5
    assert m.padded_slots == 3
    assert m.batch_fill_ratio == pytest.approx(5 / 8)
    snap = m.snapshot()
    assert snap["batches"] == 1 and snap["padded_slots"] == 3


def test_histogram_percentiles_and_decimation():
    from repro.serve.frontend.metrics import Histogram
    h = Histogram(cap=64)
    for i in range(1000):
        h.record(float(i))
    assert h.count == 1000 and h.max == 999.0
    assert h.mean == pytest.approx(499.5)
    assert len(h.samples) < 64
    assert h.percentile(50) == pytest.approx(500.0, rel=0.1)
    assert h.percentile(99) == pytest.approx(990.0, rel=0.05)


# -- open-loop simulation ---------------------------------------------------

def _stub_execute(service_s):
    def execute(server, batch):
        return [0] * len(batch.requests), service_s
    return execute


def test_sim_is_deterministic_and_conserves_requests():
    wl = poisson_workload(
        10000.0, 0.1,
        lambda rng, i: ("range_counts", np.zeros(4), (),
                        "hot" if rng.random() < 0.7 else f"t{i % 3}"),
        seed=11)
    for a in wl[::7]:
        a.deadline = 0.002                        # tight SLO: some miss
    cfg = FrontendConfig(ladder=(8, 16), max_delay=0.002, queue_limit=64)
    runs = [simulate_open_loop(None, wl, cfg, execute=_stub_execute(0.004))
            for _ in range(2)]
    (r1, m1), (r2, m2) = runs
    assert m1.snapshot() == m2.snapshot()         # bit-for-bit repeatable
    s = m1.snapshot()
    assert s["rejected"] > 0 and s["timed_out"] > 0   # overloaded on purpose
    ok = sum(r.ok for r in r1)
    assert ok + s["rejected"] + s["timed_out"] == len(wl)
    assert s["completed"] == ok
    assert [r.outcome for r in r1] == [r.outcome for r in r2]


def test_sim_latency_grows_with_load():
    def make(rng, i):
        return "range_counts", np.zeros(4), (), "default"
    cfg = FrontendConfig(ladder=(8, 16), max_delay=0.001)
    _, light = simulate_open_loop(
        None, poisson_workload(500.0, 0.2, make, seed=1), cfg,
        execute=_stub_execute(0.002))
    _, heavy = simulate_open_loop(
        None, poisson_workload(6000.0, 0.2, make, seed=1), cfg,
        execute=_stub_execute(0.002))
    assert heavy.total_s.percentile(99) > light.total_s.percentile(99)
    assert heavy.batch_fill_ratio > light.batch_fill_ratio


# -- bit-identity against the batched server --------------------------------

def test_padded_batches_bit_identical_to_direct_calls(server, qboxes, pts):
    """The acceptance bar: frontend answers == direct batched answers,
    for every kind, across padded widths, on both placements."""
    nq = qboxes.shape[0]
    reqs = [Request("range_counts", qboxes[i], ()) for i in range(nq)]
    got = execute_batch(server, Batch("range_counts", (), reqs, 16, 0.0))
    want, _ = server.range_counts(jnp.asarray(qboxes))
    assert got == [int(c) for c in np.asarray(want)]

    reqs = [Request("range_ids", qboxes[i], (256,)) for i in range(nq)]
    got = execute_batch(server, Batch("range_ids", (256,), reqs, 16, 0.0))
    ids_w, cnt_w, ov_w, _ = server.range_ids(jnp.asarray(qboxes),
                                             max_hits=256)
    ids_w, cnt_w = np.asarray(ids_w), np.asarray(cnt_w)
    ov_w = np.asarray(ov_w)
    for i in range(nq):
        np.testing.assert_array_equal(got[i][0], ids_w[i])
        assert got[i][1] == int(cnt_w[i]) and got[i][2] == bool(ov_w[i])

    reqs = [Request("knn", pts[i], (5, 256)) for i in range(nq)]
    got = execute_batch(server, Batch("knn", (5, 256), reqs, 16, 0.0))
    nn_w, d2_w, ov_w, _ = server.knn(jnp.asarray(pts), 5, max_cand=256)
    nn_w, d2_w, ov_w = np.asarray(nn_w), np.asarray(d2_w), np.asarray(ov_w)
    for i in range(nq):
        np.testing.assert_array_equal(got[i][0], nn_w[i])
        np.testing.assert_array_equal(got[i][1], d2_w[i])
        assert got[i][2] == bool(ov_w[i])


def test_split_batches_match_one_direct_batch(server, qboxes):
    """Answers are per-query: however the plane slices a stream into
    batches, the union of responses equals one direct call."""
    plane = RequestPlane(FrontendConfig(ladder=(4, 8), max_delay=0.0))
    reqs = [Request("range_counts", qboxes[i], ()) for i in
            range(qboxes.shape[0])]
    for r in reqs:
        plane.submit(r, 0.0)
    got = {}
    while plane.pending:
        batch, _ = plane.form_batch(0.0, force=True)
        for req, val in zip(batch.requests, execute_batch(server, batch)):
            got[req.seq] = val
    want, _ = server.range_counts(jnp.asarray(qboxes))
    assert [got[r.seq] for r in reqs] == [int(c) for c in np.asarray(want)]


def test_open_loop_sim_bit_identical_on_live_server(server, qboxes):
    """The bench path end to end: seeded Poisson arrivals, real
    execution, responses keyed back to their queries exactly."""
    nq = qboxes.shape[0]
    wl = poisson_workload(
        2000.0, 0.05,
        lambda rng, i: ("range_counts", qboxes[i % nq], (), "default"),
        seed=5)
    responses, metrics = simulate_open_loop(
        server, wl, FrontendConfig(ladder=(8, 16), max_delay=0.002))
    want = np.asarray(server.range_counts(jnp.asarray(qboxes))[0])
    assert all(r.ok for r in responses)
    for i, r in enumerate(responses):
        assert r.value == int(want[i % nq])
    assert metrics.completed == len(wl)


# -- the asyncio wrapper ----------------------------------------------------

def test_asyncio_frontend_serves_mixed_kinds(server, qboxes, pts):
    async def main():
        direct_counts = np.asarray(
            server.range_counts(jnp.asarray(qboxes))[0])
        nn_w, d2_w, _, _ = server.knn(jnp.asarray(pts), 3, max_cand=256)
        nn_w, d2_w = np.asarray(nn_w), np.asarray(d2_w)
        async with ServeFrontend(
                server, FrontendConfig(ladder=(16,),
                                       max_delay=0.005)) as fe:
            counts = asyncio.gather(
                *[fe.range_counts(qboxes[i], tenant=f"t{i % 3}")
                  for i in range(qboxes.shape[0])])
            knns = asyncio.gather(
                *[fe.knn(pts[i], 3, max_cand=256)
                  for i in range(pts.shape[0])])
            counts, knns = await counts, await knns
        assert all(r.ok for r in counts) and all(r.ok for r in knns)
        assert [r.value for r in counts] == [int(c) for c in direct_counts]
        for i, r in enumerate(knns):
            np.testing.assert_array_equal(r.value[0], nn_w[i])
            np.testing.assert_array_equal(r.value[1], d2_w[i])
        snap = fe.metrics.snapshot()
        assert snap["completed"] == 2 * qboxes.shape[0]
        assert snap["total_s"]["count"] == snap["completed"]
        assert set(snap["tenants"]) == {"default", "t0", "t1", "t2"}
    asyncio.run(main())


def test_asyncio_frontend_rejects_when_full(server, qboxes):
    async def main():
        fe = ServeFrontend(server, FrontendConfig(
            ladder=(4,), max_delay=0.05, queue_limit=2))
        fe.start()
        try:
            rs = await asyncio.gather(
                *[fe.range_counts(qboxes[i]) for i in range(6)])
        finally:
            await fe.close()
        outcomes = [r.outcome for r in rs]
        assert outcomes.count(Outcome.REJECTED) >= 1
        assert all(o in (Outcome.OK, Outcome.REJECTED) for o in outcomes)
    asyncio.run(main())


def test_asyncio_handoff_one_sample_per_batch():
    """``handoff_s`` (batch formed -> the worker starts it) takes one
    sample per batch, read on the frontend's clock: on a virtual clock
    that never moves, each sample is exactly 0."""
    class Counts:
        def range_counts(self, qboxes):
            return jnp.zeros(qboxes.shape[0], jnp.int32), {}

    async def main():
        fe = ServeFrontend(Counts(), FrontendConfig(ladder=(4,),
                                                    max_delay=0.0))
        fe.clock = VirtualClock(7.0)
        async with fe:
            rs = await asyncio.gather(
                *[fe.range_counts(np.zeros(4)) for _ in range(10)])
        assert all(r.ok for r in rs)
        return fe.metrics

    m = asyncio.run(main())
    assert m.batches >= 3 and m.handoff_s.count == m.batches
    assert m.handoff_s.samples == [0.0] * m.batches
    assert m.snapshot()["handoff_s"]["count"] == m.batches


def test_asyncio_close_drains_pending(server, qboxes):
    async def main():
        fe = ServeFrontend(server, FrontendConfig(
            ladder=(64,), max_delay=30.0))     # never due on its own
        fe.start()
        futs = [asyncio.ensure_future(fe.range_counts(qboxes[i]))
                for i in range(4)]
        await asyncio.sleep(0)                 # let submits land
        await fe.close()                       # force-drains
        rs = await asyncio.gather(*futs)
        assert all(r.ok for r in rs)
    asyncio.run(main())


# -- SPMD: the frontend over a real mesh ------------------------------------

@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (CI virtual-device job)")
def test_frontend_spmd_mesh_bit_identical(mbrs, qboxes):
    """Frontend batches through a sharded server on a real 8-device
    mesh: same answers as the single-device direct call."""
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:8]), ("d",))
    srv = SpatialServer.from_method(
        "bsp", mbrs, PAYLOAD,
        ServeConfig(placement="sharded", shards=8), mesh=mesh)
    plain = SpatialServer.from_method("bsp", mbrs, PAYLOAD)
    nq = qboxes.shape[0]
    reqs = [Request("range_ids", qboxes[i], (256,)) for i in range(nq)]
    got = execute_batch(srv, Batch("range_ids", (256,), reqs, 16, 0.0))
    ids_w, cnt_w, _, _ = plain.range_ids(jnp.asarray(qboxes), max_hits=256)
    ids_w, cnt_w = np.asarray(ids_w), np.asarray(cnt_w)
    for i in range(nq):
        np.testing.assert_array_equal(got[i][0], ids_w[i])
        assert got[i][1] == int(cnt_w[i])
