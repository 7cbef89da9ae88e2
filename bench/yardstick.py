"""Arithmetic the metrics share, kept with the benchmark.

``skew_ratio`` is a numpy copy of ``repro.core.metrics.skew_ratio``
and ``CompileClock`` a copy of ``chip_smoke.CompileClock``; the rest is
the benchmark's own: how many objects each partition box meets, which
partition boxes each query box meets, and the least bytes a range
batch has to move.
"""
from __future__ import annotations

import numpy as np

OBJECT_BYTES = 16          # one f32 box
QUERY_BYTES = 16
ANSWER_BYTES = 4           # one int32 count or id


def skew_ratio(counts: np.ndarray, valid: np.ndarray) -> float:
    """max / mean payload over the valid partitions (the paper's skew,
    the SPMD straggler factor)."""
    c = np.where(valid, counts, 0).astype(np.float64)
    k = max(int(valid.sum()), 1)
    return float(c.max() / max(c.sum() / k, 1e-9))


def meets(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(A, 4) x (B, 4) closed boxes -> (A, B) bool intersection."""
    return ((a[:, None, 0] <= b[None, :, 2]) & (b[None, :, 0] <= a[:, None, 2])
            & (a[:, None, 1] <= b[None, :, 3])
            & (b[None, :, 1] <= a[:, None, 3]))


def tile_objects(mbrs: np.ndarray, boxes: np.ndarray, valid: np.ndarray,
                 block: int = 16):
    """Objects meeting each partition box (multi-assignment) and, per
    box, the objects whose first (lowest-index) valid box it is
    -> (overlap (T,), first (T,)) int64."""
    t = len(boxes)
    overlap = np.zeros(t, np.int64)
    first = np.full(len(mbrs), t, np.int64)
    for lo in range(0, t, block):
        hit = meets(mbrs, boxes[lo:lo + block]) & valid[None, lo:lo + block]
        overlap[lo:lo + block] = hit.sum(axis=0)
        has = hit.any(axis=1) & (first == t)
        first[has] = lo + np.argmax(hit[has], axis=1)
    return overlap, np.bincount(first, minlength=t + 1)[:t]


def fanout(qboxes: np.ndarray, boxes: np.ndarray,
           valid: np.ndarray) -> np.ndarray:
    """Valid partition boxes each query box meets -> (Q,) int."""
    return (meets(qboxes, boxes) & valid[None, :]).sum(axis=1)


def range_batch_bytes(qboxes: np.ndarray, n_answers: int, boxes: np.ndarray,
                      valid: np.ndarray, first: np.ndarray) -> int:
    """Least bytes a range batch moves: every object of the tiles whose
    partition box meets a query of the batch, each counted once (by the
    tile that holds its first copy), plus the query boxes and the
    answers (``n_answers`` int32 values)."""
    touched = (meets(qboxes, boxes) & valid[None, :]).any(axis=0)
    return int(OBJECT_BYTES * first[touched].sum()
               + QUERY_BYTES * len(qboxes) + ANSWER_BYTES * n_answers)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and the
    persistent-cache hits, read from ``jax.monitoring`` events."""

    def __init__(self, jax):
        self.secs, self.hits, self.events = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event.startswith("/jax/core/compile/"):
            self.secs += duration_secs
        if event == "/jax/core/compile/backend_compile_duration":
            self.events += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
