"""The serving path's host spans, read back from a profiler trace
recorded on the CPU: which spans a batch leaves, on which thread, what
they carry, and how they nest."""
import asyncio
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.data import spatial_gen
from repro.serve import SpatialServer
from repro.serve.frontend import FrontendConfig, ServeFrontend

N, PAYLOAD = 1500, 130
WORKER_SPANS = ("serve.fanout_stats", "serve.route", "serve.heat",
                "serve.probe", "serve.fetch")


def _serve(server, qboxes, pts):
    async def main():
        async with ServeFrontend(server, FrontendConfig(
                ladder=(8,), max_delay=0.002)) as fe:
            rs = await asyncio.gather(
                *[fe.range_counts(q) for q in qboxes],
                *[fe.range_ids(q, 64) for q in qboxes],
                *[fe.knn(p, 3, max_cand=256) for p in pts])
        assert all(r.ok for r in rs)
        return fe.metrics
    return asyncio.run(main())


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """-> ({line index: [(name, start_ns, end_ns, stats)]} of the
    ``serve.*`` spans, the frontend's metrics)."""
    rng = np.random.default_rng(0)
    mbrs = spatial_gen.dataset("osm", jax.random.PRNGKey(0), N)
    server = SpatialServer.from_method("bsp", mbrs, PAYLOAD)
    c = rng.uniform(0.1, 0.9, (12, 2)).astype(np.float32)
    qboxes = np.concatenate([c - 0.05, c + 0.05], axis=1)
    pts = rng.uniform(0, 1, (5, 2)).astype(np.float32)
    _serve(server, qboxes, pts)          # compile outside the trace
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        metrics = _serve(server, qboxes, pts)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                      dict(e.stats)) for e in line.events
                     if e.name.startswith("serve.")]
            if spans:
                lines[(plane.name, i)] = spans
    return lines, metrics


def _by_name(lines, name):
    return [(line, s) for line, spans in lines.items() for s in spans
            if s[0] == name]


def test_each_batch_leaves_its_spans(traced):
    lines, metrics = traced
    names = {s[0] for spans in lines.values() for s in spans}
    assert {"serve.form", "serve.execute", "serve.respond",
            *WORKER_SPANS} <= names
    executes = _by_name(lines, "serve.execute")
    assert len(executes) == metrics.batches
    assert {s[3]["kind"] for _, s in executes} == {
        "range_counts", "range_ids", "knn"}
    assert all(s[3]["width"] == 8 for _, s in executes)


def test_spans_of_one_batch_share_its_number(traced):
    lines, _ = traced
    executes = _by_name(lines, "serve.execute")
    forms = {s[3]["batch"]: (line, s)
             for line, s in _by_name(lines, "serve.form") if s[3]}
    responds = {s[3]["batch"]: line
                for line, s in _by_name(lines, "serve.respond")}
    numbers = sorted(s[3]["batch"] for _, s in executes)
    assert numbers == list(range(len(executes)))
    for line, s in executes:
        form_line, form = forms[s[3]["batch"]]
        # formed on the dispatcher's thread, executed on the worker's
        assert form_line != line and responds[s[3]["batch"]] == form_line
        assert form[3]["kind"] == s[3]["kind"]
        assert form[2] <= s[1]                 # formed before it ran
        assert 1 <= form[3]["fill"] <= form[3]["width"]


def test_server_spans_nest_inside_execute(traced):
    lines, _ = traced
    for line, spans in lines.items():
        executes = [s for s in spans if s[0] == "serve.execute"]
        routes = [s for s in spans if s[0] == "serve.route"]
        for name, lo, hi, _ in spans:
            if name in WORKER_SPANS:
                assert any(e[1] <= lo and hi <= e[2] for e in executes), name
            if name == "serve.heat":
                assert any(r[1] <= lo and hi <= r[2] for r in routes)


def test_handoff_has_one_sample_per_batch(traced):
    _, metrics = traced
    assert metrics.handoff_s.count == metrics.batches > 0
    assert metrics.snapshot()["handoff_s"]["count"] == metrics.batches
