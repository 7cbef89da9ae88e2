"""A run of one cell on the CPU, through the functions ``run.py``
drives, at a test-only size (``data/tiny*.json``): the result line's
keys, ``correct`` on sound answers, and ``correct`` false where the
timed path is broken underneath or the control takes its place."""
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import control, harness, oracle  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CFG = harness.read_json(os.path.join(DATA, "tiny.json"))
TRAFFIC = harness.read_json(os.path.join(DATA, "tiny-open.json"))
CLOSED = harness.read_json(os.path.join(DATA, "tiny-closed.json"))
SEED = 3_000_000_019          # more than 32 bits hold
E2E = [("setup_s", "s"), ("qps", "requests/s")]


def run(hook=None, traffic=TRAFFIC) -> dict:
    out = harness.run_cell(jax, CFG, traffic, SEED, 1.5, False, E2E,
                           time.perf_counter(), jax.devices()[:1],
                           server_hook=hook)
    return json.loads(harness.result_line(out))


class Broken:
    """The server with each batch's answers spoilt by ``spoil(kind,
    out, n)``, where ``n`` counts the batch's real (unpadded) rows."""

    def __init__(self, server, spoil):
        self._server, self._spoil = server, spoil

    def __getattr__(self, name):
        return getattr(self._server, name)

    def range_counts(self, qboxes, *a, **kw):
        out = self._server.range_counts(qboxes, *a, **kw)
        return self._spoil("range_counts", out, _real(qboxes))

    def range_ids(self, qboxes, *a, **kw):
        out = self._server.range_ids(qboxes, *a, **kw)
        return self._spoil("range_ids", out, _real(qboxes))

    def knn(self, pts, *a, **kw):
        out = self._server.knn(pts, *a, **kw)
        uni = np.asarray(self._server.uni)
        pad = (uni[:2] + uni[2:]) * 0.5
        n = int((np.asarray(pts) != pad).any(axis=1).sum())
        return self._spoil("knn", out, n)


def _real(qboxes) -> int:
    return int((np.asarray(qboxes)[:, 0] < 1e9).sum())


def altered(kind, out, n):
    """Every answer altered where it is produced."""
    if kind == "range_counts":
        return (out[0] + 1,) + tuple(out[1:])
    if kind == "range_ids":
        return (out[0], out[1] + 1) + tuple(out[2:])
    return (jnp.where(out[0] >= 0, out[0] + 1, out[0]),) + tuple(out[1:])


def half_left_out(kind, out, n):
    """The answers of the back half of each batch's requests left out
    (as empty answers)."""
    keep = jnp.arange(out[0].shape[0]) < n // 2
    first = out[0]
    if kind == "range_counts":
        return (jnp.where(keep, first, 0),) + tuple(out[1:])
    if kind == "range_ids":
        return (jnp.where(keep[:, None], first, -1),
                jnp.where(keep, out[1], 0)) + tuple(out[2:])
    return (jnp.where(keep[:, None], first, -1),) + tuple(out[1:])


@pytest.mark.parametrize("traffic", [TRAFFIC, CLOSED], ids=["open", "closed"])
def test_sound_run_is_correct_and_its_line_is_whole(traffic):
    got = run(traffic=traffic)
    assert list(got) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert got["correct"] is True
    assert got["failed"] == 0
    if traffic["loop"] == "open":
        assert got["attempted"] == round(traffic["rate"] * 1.5)
    else:
        assert got["attempted"] >= traffic["clients"]
    assert set(got["metrics"]) == {name for name, _ in E2E}
    assert all(m["value"] > 0 for m in got["metrics"].values())
    assert set(got["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert set(got["checks"]) == set(oracle.LIMITS)


@pytest.mark.parametrize("spoil", [altered, half_left_out],
                         ids=["answer_altered", "half_batch_left_out"])
def test_broken_timed_path_is_not_correct(spoil):
    got = run(lambda server: Broken(server, spoil))
    assert got["correct"] is False


def test_control_is_not_correct():
    numbers = control.control_numbers(jax, CFG, TRAFFIC, SEED, 1.5)
    assert not oracle.verdict(numbers)
    assert numbers["knn_d2_gap"] > oracle.LIMITS["knn_d2_gap"]


def test_reference_holds_itself_correct():
    rng = np.random.default_rng(0)
    mbrs = rng.uniform(0, 1, (500, 4)).astype(np.float32)
    mbrs[:, 2:] = mbrs[:, :2] + 0.01
    q = mbrs[:8].copy()
    pts = mbrs[:8, :2].copy()
    hits = oracle.range_ref(mbrs, q)
    ids = [(np.pad(h, (0, 64 - len(h)), constant_values=-1), len(h), False)
           for h in hits]
    nn, d2 = oracle.knn_ref(mbrs, pts, 5)
    numbers = oracle.compare(mbrs, {
        "range_counts": (q, [len(h) for h in hits]),
        "range_ids": (q, ids), "knn": (pts, 5, list(zip(nn, d2)))}, 0)
    assert oracle.verdict(numbers)
    assert numbers == {"unanswered": 0, "range_wrong": 0, "knn_wrong": 0,
                       "knn_d2_gap": 0.0}
