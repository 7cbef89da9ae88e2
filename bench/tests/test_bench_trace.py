"""The trace reduction (``bench/xplane.py``) on a synthetic trace with
known answers, and its loader on a trace recorded on the CPU."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import xplane  # noqa: E402

# one device: ops at [0,100), [150,200), [180,220) -> busy 170 of 300
TRACE = {
    "devices": {"/device:TPU:0": [("fusion.1", 0, 100),
                                  ("probe_kernel", 150, 50),
                                  ("fusion.2", 180, 40)]},
    "spans": [("bench.window", 0, 300),
              ("bench.range_counts", 90, 100),
              ("bench.range_ids", 200, 20)],
}


def test_busy_union_clips_and_merges():
    ev = TRACE["devices"]["/device:TPU:0"]
    assert xplane.merged(ev, 0, 300) == [[0, 100], [150, 220]]
    assert xplane.busy_ns(ev, 0, 300) == 170
    assert xplane.busy_ns(ev, 50, 160) == 60


def test_gaps_and_idle_attribution():
    ev = TRACE["devices"]["/device:TPU:0"]
    assert xplane.gaps(ev, 0, 300) == [(100, 150), (220, 300)]
    red = xplane.reduce(TRACE, (0, 300))
    assert red["busy_s"] == pytest.approx(170e-9)
    assert red["window_s"] == pytest.approx(300e-9)
    assert red["idle_share"] == pytest.approx(130 / 300)
    # gap (100,150) falls in the range_counts span; (220,300) in none
    assert dict(red["idle_gaps"]) == pytest.approx(
        {"no span": 80e-9, "bench.range_counts": 50e-9})
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(100e-9)]


def test_two_devices_average():
    two = dict(TRACE, devices={"/device:TPU:0": [("a", 0, 100)],
                               "/device:TPU:1": [("a", 0, 200)]})
    red = xplane.reduce(two, (0, 400))
    assert red["busy_s"] == pytest.approx(150e-9)
    assert red["idle_share"] == pytest.approx(250 / 400)


def test_per_span_assigns_by_midpoint():
    got = xplane.per_span(TRACE, "bench.range_counts",
                         lambda name: "probe" in name)
    assert got == [50]
    # fusion.2 [180, 220) has its midpoint in the range_ids span
    assert xplane.per_span(TRACE, "bench.range_ids", lambda n: True) == [40]


def test_window_of_wants_one_window():
    assert xplane.window_of(TRACE["spans"]) == (0, 300)
    with pytest.raises(ValueError):
        xplane.window_of(TRACE["spans"] * 2)


def test_reduce_refuses_a_trace_without_device_ops():
    with pytest.raises(ValueError):
        xplane.reduce({"devices": {}, "spans": []}, (0, 1))


def test_load_reads_bench_spans_from_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    x = jnp.ones(8)
    f(x).block_until_ready()
    xplane.start(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.range_counts"):
                f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    got = xplane.load(xplane.find(str(tmp_path)))
    names = sorted(n for n, _, _ in got["spans"])
    assert names == ["bench.range_counts", "bench.window"]
    lo, hi = xplane.window_of(got["spans"])
    assert hi > lo
