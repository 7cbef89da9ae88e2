"""The plain reference and the comparison that decides ``correct``.

The reference is numpy brute force over the run's own objects, copied
from the program's oracles (``query.range.range_query_ref``,
``query.knn.knn_ref``) and blocked so that it fits: it imports nothing
of the program.  Every configuration states one guarantee, that every
answer is exact, so each number compared below is a count of answers
that break it, with the limit 0, except the kNN distance gap, whose
limit allows the device's float32 rounding.

The control (``control_answers``) is the same reference on objects and
queries rounded to bfloat16, the precision below the float32 the
configurations state: approximate answers that the comparison has to
refuse.
"""
from __future__ import annotations

import numpy as np

# number compared -> limit.  Counts of wrong answers are exact (0).
# knn_d2_gap: the largest relative gap between a served squared
# distance and the reference's; sound runs read 0 on the chip and a
# few ulps on the CPU, the bfloat16 control reads several units
# (PERF.md gives the readings this limit was set from).
LIMITS = {"unanswered": 0, "range_wrong": 0, "knn_wrong": 0,
          "knn_d2_gap": 1e-5}

_BLOCK = 32


def range_ref(mbrs: np.ndarray, qboxes: np.ndarray) -> list:
    """Per query the ascending ids of the objects its box meets."""
    out = []
    for lo in range(0, len(qboxes), _BLOCK):
        q = qboxes[lo:lo + _BLOCK]
        hit = ((q[:, None, 0] <= mbrs[None, :, 2])
               & (mbrs[None, :, 0] <= q[:, None, 2])
               & (q[:, None, 1] <= mbrs[None, :, 3])
               & (mbrs[None, :, 1] <= q[:, None, 3]))
        out.extend(np.flatnonzero(row).astype(np.int32) for row in hit)
    return out


def mindist2(mbrs: np.ndarray, pts: np.ndarray) -> np.ndarray:
    """(Q, 2) points x (N, 4) boxes -> (Q, N) squared MINDIST."""
    px, py = pts[:, None, 0], pts[:, None, 1]
    dx = np.maximum(np.maximum(mbrs[None, :, 0] - px, px - mbrs[None, :, 2]),
                    0.0)
    dy = np.maximum(np.maximum(mbrs[None, :, 1] - py, py - mbrs[None, :, 3]),
                    0.0)
    return dx * dx + dy * dy


def knn_ref(mbrs: np.ndarray, pts: np.ndarray, k: int):
    """(Q, k) ids and squared distances ordered by (distance, id)."""
    ids = np.empty((len(pts), k), np.int32)
    d2s = np.empty((len(pts), k), np.float32)
    for lo in range(0, len(pts), _BLOCK):
        d2 = mindist2(mbrs, pts[lo:lo + _BLOCK])
        kth = np.partition(d2, k - 1, axis=1)[:, k - 1]
        for j, row in enumerate(d2):
            near = np.flatnonzero(row <= kth[j])
            order = near[np.lexsort((near, row[near]))][:k]
            ids[lo + j], d2s[lo + j] = order, row[order]
    return ids, d2s


def compare(mbrs: np.ndarray, checked: dict, unanswered: int) -> dict:
    """Hold the served answers in ``checked`` to the reference.

    checked: ``range_counts`` -> (qboxes (m, 4), counts (m,)),
    ``range_ids`` -> (qboxes, [(ids, count, overflow)]), ``knn`` ->
    (pts (m, 2), k, [(ids (k,), d2 (k,))]); any may be absent.
    -> {number: value} for every key of ``LIMITS``.
    """
    wrong_range = wrong_knn = 0
    gap = 0.0
    if "range_counts" in checked:
        qb, counts = checked["range_counts"]
        want = range_ref(mbrs, qb)
        wrong_range += sum(int(c) != len(w) for c, w in zip(counts, want))
    if "range_ids" in checked:
        qb, answers = checked["range_ids"]
        want = range_ref(mbrs, qb)
        for (ids, count, overflow), w in zip(answers, want):
            mh = len(ids)
            ok = (int(count) == len(w) and bool(overflow) == (len(w) > mh)
                  and np.array_equal(ids[ids >= 0], w[:mh]))
            wrong_range += not ok
    if "knn" in checked:
        pts, k, answers = checked["knn"]
        want_ids, want_d2 = knn_ref(mbrs, pts, k)
        for (ids, d2), wi, wd in zip(answers, want_ids, want_d2):
            ids, d2 = np.asarray(ids), np.asarray(d2, np.float32)
            # the served set must be the reference's; its order is
            # held by the distances, since float32 rounding on the
            # device may swap two neighbours equal to rounding
            wrong_knn += not np.array_equal(np.sort(ids), np.sort(wi))
            rel = np.abs(np.sort(d2) - wd) / np.maximum(wd, 1e-30)
            gap = max(gap, float(np.max(rel, initial=0.0)))
    return {"unanswered": int(unanswered), "range_wrong": int(wrong_range),
            "knn_wrong": int(wrong_knn), "knn_d2_gap": gap}


def verdict(numbers: dict) -> bool:
    """Every number at or under its limit."""
    return all(numbers[k] <= lim for k, lim in LIMITS.items())


def checks_line(numbers: dict) -> dict:
    """{number: [value, limit]} for the result line's last key."""
    return {k: [numbers[k], LIMITS[k]] for k in LIMITS}


def to_bf16(a: np.ndarray) -> np.ndarray:
    """Round float32 values to bfloat16 (round to nearest even) and
    back to float32."""
    bits = np.ascontiguousarray(a, np.float32).view(np.uint32)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32)


def control_answers(mbrs: np.ndarray, queries: dict, max_hits: int = 1024,
                    k: int = 10) -> dict:
    """The control: the reference computed on bfloat16-rounded objects
    and queries.  queries: {kind: (m, 4) boxes or (m, 2) points}
    -> answers in the shape ``compare`` takes."""
    lo = to_bf16(mbrs)
    out = {}
    for kind, q in queries.items():
        if kind == "knn":
            ids, d2 = knn_ref(lo, to_bf16(q), k)
            out[kind] = (q, k, list(zip(ids, d2)))
            continue
        hits = range_ref(lo, to_bf16(q))
        if kind == "range_counts":
            out[kind] = (q, [len(h) for h in hits])
            continue
        answers = []
        for h in hits:
            ids = np.full((max_hits,), -1, np.int32)
            ids[:min(max_hits, len(h))] = h[:max_hits]
            answers.append((ids, len(h), len(h) > max_hits))
        out[kind] = (q, answers)
    return out
