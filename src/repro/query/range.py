"""Batched range (box-containment/overlap) queries over staged layouts.

A range query is a box; its answer is the set of dataset objects whose
MBR intersects it (closed-box ``st_intersects``, matching the join
path).  Queries run against the ``repro.serve.layout`` staging format
(``stage_tiles``): ``(T, cap, 4)`` member-box tiles built by MASJ
assignment — once per dataset, then kept current by the streaming
append path (which only ever grows canonical membership and the boxes
that summarise it, so everything here stays exact on a moving
dataset).

Replication makes dedup the correctness crux (same problem as the join,
§2.2), solved two ways, mirroring the join engine:

- **canonical-copy** (primary, all layouts): staging marks exactly one
  copy of every object as canonical; probing only canonical copies
  yields exact unique counts *and* exact unique id sets with zero dedup
  work, because a hit test against a member's full MBR is
  tile-independent.  This is the dense throughput path — one
  ``range_probe`` kernel sweep over all local tiles.
- **reference-point** (zero-extra-state, non-overlapping covering
  layouts only): a (query, object) hit is owned by the tile containing
  the intersection's low corner, so owned counts are exact without any
  canonical marking.  Overlapping tight-MBR layouts (HC/STR) can own a
  hit in several tiles — those must use the canonical path (same
  Table-1 split as the join's dedup-mode choice).

The global index (``repro.serve.router``) prunes which tiles a query
*must* visit, and per-query fan-out is the paper's boundary-object cost
metric for selection workloads.  Three pruned executors exploit it:

- ``pruned_range_counts`` / ``pruned_range_ids`` (primary): probe only
  each query's ``(Q, F)`` candidate tiles with the gathered
  ``range_probe`` kernel, against **canonical** tiles routed on
  canonical probe boxes — exact unique answers on *all six layouts*
  (see ``serve.router``), O(Q·F·cap) work instead of O(Q·T·cap).
- ``routed_range_counts`` (rp variant): candidate gather with
  reference-point ownership over the *full* tiles — exact for
  non-overlapping covering layouts without any canonical marking.

These executors are placement-agnostic — pure functions of staged
arrays, consumed through the ``TileLayout`` protocol
(``repro.serve.layout``) by both data placements.  When tiles are
*sharded* across devices (``repro.serve.exchange``), each owner runs
the pruned executors above on its local shard only and the home device
reduces the partials: ``merge_owner_counts`` (plain
integer sum — canonical copies make hits owner-disjoint) and
``merge_owner_ids`` (duplicate-free union by one ascending sort).
Merged answers are bit-identical to the single-device dense sweep.

Device operations carry two ``jax.named_scope`` names that do not move
with the shapes: ``probe`` (the gathered probe kernel's call) and
``idcompact`` (the id compaction of every id path: keyed id table,
sort, slice).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core import geometry
from ..kernels.range_probe import ops as rops
from .join import rp_own_mask

_BIG_ID = jnp.int32(2**30)


# --------------------------------------------------------------------------
# brute-force reference (numpy, host)
# --------------------------------------------------------------------------

def range_query_ref(mbrs: np.ndarray, qboxes: np.ndarray) -> list[np.ndarray]:
    """Per-query sorted hit-id arrays, numpy brute force (oracle)."""
    out = []
    for q in qboxes:
        hit = ((q[0] <= mbrs[:, 2]) & (mbrs[:, 0] <= q[2])
               & (q[1] <= mbrs[:, 3]) & (mbrs[:, 1] <= q[3]))
        out.append(np.flatnonzero(hit).astype(np.int32))
    return out


# --------------------------------------------------------------------------
# canonical-copy path (exact for every layout)
# --------------------------------------------------------------------------

@jax.jit
def range_counts(qboxes: jax.Array, canon_tiles: jax.Array,
                 alive: jax.Array | None = None) -> jax.Array:
    """Exact per-query unique hit counts.

    qboxes: (Q, 4); canon_tiles: (T, cap, 4) canonical-copy member boxes
    (non-canonical slots sentineled) -> (Q,) int32.  ``alive``: (T, cap)
    bool tombstone mask — deleted objects stop answering.
    """
    return jnp.sum(rops.probe_counts(qboxes, canon_tiles, alive=alive),
                   axis=1)


@functools.partial(jax.jit, static_argnames=("max_hits",))
def range_ids(qboxes: jax.Array, canon_tiles: jax.Array, ids: jax.Array,
              max_hits: int, alive: jax.Array | None = None
              ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Exact per-query unique hit-id sets, ascending, padded with -1.

    ids: (T, cap) int32 member ids (-1 in padding slots).  Returns
    ``(hit_ids[Q, max_hits], counts[Q], overflow[Q])``; ids beyond
    ``max_hits`` are dropped and flagged.  ``alive`` as in
    ``range_counts``.
    """
    q = qboxes.shape[0]
    mask = rops.probe_mask(qboxes, canon_tiles, alive=alive)  # (Q, T, cap)
    flat = mask.reshape(q, -1) & (ids.reshape(-1) >= 0)[None, :]
    with jax.named_scope("idcompact"):
        keyed = jnp.where(flat, ids.reshape(-1)[None, :], _BIG_ID)
        if keyed.shape[1] < max_hits:      # small layout, wide id budget
            keyed = jnp.pad(keyed,
                            ((0, 0), (0, max_hits - keyed.shape[1])),
                            constant_values=_BIG_ID)
        top = jax.lax.sort(keyed, dimension=1)[:, :max_hits]
    hit_ids = jnp.where(top < _BIG_ID, top, -1)
    counts = jnp.sum(flat, axis=1, dtype=jnp.int32)
    return hit_ids, counts, counts > max_hits


# --------------------------------------------------------------------------
# pruned canonical path (exact for every layout, routed work only)
# --------------------------------------------------------------------------

@jax.jit
def pruned_range_counts(qboxes: jax.Array, canon_tiles: jax.Array,
                        cand: jax.Array,
                        chunk_boxes: jax.Array | None = None,
                        alive: jax.Array | None = None) -> jax.Array:
    """Exact per-query unique hit counts, probing candidate tiles only.

    qboxes: (Q, 4); canon_tiles: (T, cap, 4) canonical-copy member
    boxes; cand: (Q, F) int32 from ``serve.router.candidate_range``
    over the layout's canonical probe boxes (-1 = padding slot)
    -> (Q,) int32.  ``chunk_boxes`` (T, C, 4), when given (indexed
    staging, ``local_index="x"``/``"hilbert"``), switches to the
    chunk-skipping kernel — same bits, dead 128-member chunks skipped.

    Exactness: every canonical copy an un-pruned sweep would hit lives
    in a tile whose probe box the query overlaps, so a candidate list
    without overflow loses nothing; padded (-1) candidates gather an
    all-sentinel tile and contribute zero.  Chunk boxes bound their
    chunks' canonical members (a staging invariant), so a skipped
    chunk provably holds no hit.  ``alive``: (T, cap) tombstone mask.
    """
    with jax.named_scope("probe"):
        if chunk_boxes is None:
            per_tile = rops.gathered_counts(qboxes, canon_tiles, cand,
                                            alive=alive)
        else:
            per_tile = rops.gathered_counts_skip(qboxes, canon_tiles,
                                                 chunk_boxes, cand,
                                                 alive=alive)
    return jnp.sum(per_tile, axis=1)


@functools.partial(jax.jit, static_argnames=("max_hits",))
def pruned_range_ids(qboxes: jax.Array, canon_tiles: jax.Array,
                     ids: jax.Array, cand: jax.Array, max_hits: int,
                     chunk_boxes: jax.Array | None = None,
                     alive: jax.Array | None = None
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Exact per-query unique hit-id sets from candidate tiles only.

    Same contract as ``range_ids`` (ascending ids, -1 padded, overflow
    flagged past ``max_hits``) at O(Q·F·cap) instead of O(Q·T·cap):
    ids: (T, cap) int32 (-1 padding); cand: (Q, F) int32 (-1 padding)
    -> ``(hit_ids[Q, max_hits], counts[Q], overflow[Q])``.
    ``chunk_boxes`` selects the chunk-skipping mask kernel (see
    ``pruned_range_counts``).

    Uniqueness is free: each object has exactly one canonical slot
    repo-wide, and a candidate list names distinct tiles, so no id can
    appear twice in the gathered hit table.
    """
    q = qboxes.shape[0]
    with jax.named_scope("probe"):
        if chunk_boxes is None:
            mask = rops.gathered_mask(qboxes, canon_tiles, cand,
                                      alive=alive)            # (Q, F, cap)
        else:
            mask = rops.gathered_mask_skip(qboxes, canon_tiles,
                                           chunk_boxes, cand, alive=alive)
    gids = rops.gathered_ids(ids, cand)                    # (Q, F, cap)
    flat = mask.reshape(q, -1) & (gids.reshape(q, -1) >= 0)
    with jax.named_scope("idcompact"):
        keyed = jnp.where(flat, gids.reshape(q, -1), _BIG_ID)
        if keyed.shape[1] < max_hits:      # narrow gather, wide id budget
            keyed = jnp.pad(keyed,
                            ((0, 0), (0, max_hits - keyed.shape[1])),
                            constant_values=_BIG_ID)
        top = jax.lax.sort(keyed, dimension=1)[:, :max_hits]
    hit_ids = jnp.where(top < _BIG_ID, top, -1)
    counts = jnp.sum(flat, axis=1, dtype=jnp.int32)
    return hit_ids, counts, counts > max_hits


# --------------------------------------------------------------------------
# owner-partial merges (the sharded executor's home-side reduce)
# --------------------------------------------------------------------------

def merge_owner_counts(partials: jax.Array, slots: jax.Array,
                       qpd: int) -> jax.Array:
    """Sum per-owner partial counts back onto home query slots.

    partials: (D, M) int32 — entry (o, m) is owner ``o``'s count for
    this home's ``m``-th message to it; slots: (D, M) int32 home query
    slot each message carries (-1 = padding) -> (qpd,) int32.

    Exact because canonical copies partition the id space across tiles
    and the placement partitions tiles across owners: every hit is
    counted by exactly one owner, so the merge is a plain integer sum
    (associative — deterministic under any scatter order).  Dead
    messages land in a trash row that is sliced off.
    """
    live = slots >= 0
    idx = jnp.where(live, slots, qpd)
    return jnp.zeros((qpd + 1,), jnp.int32).at[idx].add(
        jnp.where(live, partials, 0))[:qpd]


def merge_owner_ids(pids: jax.Array, pcounts: jax.Array, slots: jax.Array,
                    qpd: int, max_hits: int
                    ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Union per-owner sorted id partials into the ``range_ids`` contract.

    pids: (D, M, mh) ascending local hit ids (-1 padded) from each
    owner; pcounts: (D, M) true (untruncated) local counts; slots:
    (D, M) home query slots (-1 padding) -> ``(hit_ids[qpd, max_hits],
    counts[qpd], overflow[qpd])``.

    Each query reaches each owner at most once and each canonical id
    lives on exactly one owner, so the union is duplicate-free: scatter
    the ≤ D partial lists into a per-query table and one ascending sort
    yields exactly the dense path's id set.  Local truncation (an owner
    holding more than ``mh`` hits) implies ``counts > max_hits`` when
    ``mh == max_hits``, so it is always flagged, never silent.
    """
    d, _, mh = pids.shape
    live = slots >= 0
    idx = jnp.where(live, slots, qpd)
    col = jnp.arange(d)[:, None]
    with jax.named_scope("idcompact"):
        keyed = jnp.where(live[..., None] & (pids >= 0), pids, _BIG_ID)
        tbl = jnp.full((qpd + 1, d, mh), _BIG_ID,
                       jnp.int32).at[idx, col].set(keyed)
        flat = tbl[:qpd].reshape(qpd, d * mh)
        if flat.shape[1] < max_hits:
            flat = jnp.pad(flat, ((0, 0), (0, max_hits - flat.shape[1])),
                           constant_values=_BIG_ID)
        top = jax.lax.sort(flat, dimension=1)[:, :max_hits]
    hit_ids = jnp.where(top < _BIG_ID, top, -1)
    counts = merge_owner_counts(pcounts, slots, qpd)
    return hit_ids, counts, counts > max_hits


# --------------------------------------------------------------------------
# reference-point path (non-overlapping covering layouts)
# --------------------------------------------------------------------------

@jax.jit
# reprolint: disable=kernel-twin-parity -- reference-point research path
# over full MASJ tiles of a static layout; not part of the tombstone
# serving surface (serving goes through range_counts/pruned_*)
def range_counts_rp(qboxes: jax.Array, tiles: jax.Array,
                    tile_boxes: jax.Array, uni: jax.Array) -> jax.Array:
    """Exact unique counts via reference-point ownership (FG/BSP/SLC/BOS).

    tiles: the *full* MASJ tiles — no canonical marking needed; each hit
    is counted only in the tile owning the intersection's low corner.
    """
    hits = rops.probe_mask(qboxes, tiles)                 # (Q, T, cap)
    own = jax.vmap(
        lambda member_boxes, tb: rp_own_mask(qboxes, member_boxes, tb, uni)
    )(tiles, tile_boxes)                                  # (T, Q, cap)
    own = jnp.swapaxes(own, 0, 1)
    return jnp.sum(hits & own, axis=(1, 2), dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("max_fanout",))
# reprolint: disable=kernel-twin-parity -- reference-point research path
# (see range_counts_rp): static layouts only, outside the tombstone
# serving surface
def routed_range_counts(qboxes: jax.Array, tiles: jax.Array,
                        tile_boxes: jax.Array, uni: jax.Array,
                        route_mask: jax.Array, max_fanout: int) -> jax.Array:
    """Pruned probe: each query gathers only its routed tiles.

    ``route_mask``: (Q, T) bool from ``serve.router.route_range``.  Work
    is O(Q · max_fanout · cap) instead of O(Q · T · cap) — the win the
    paper's fan-out metric predicts.  Exact for non-overlapping covering
    layouts (rp ownership).  Returns ``(counts[Q], overflow[Q])``;
    queries routed to more than ``max_fanout`` tiles undercount and are
    flagged, never silently truncated.
    """
    fanout = jnp.sum(route_mask, axis=1, dtype=jnp.int32)
    order = jnp.argsort(~route_mask, axis=1, stable=True)  # routed first
    routed = order[:, :max_fanout]                         # (Q, F)
    live = jnp.take_along_axis(route_mask, routed, axis=1)  # (Q, F)

    def per_query(qbox, tidx, tlive):
        tb = tile_boxes[tidx]                              # (F, 4)
        mb = tiles[tidx]                                   # (F, cap, 4)
        hits = jax.vmap(
            lambda boxes, box: (rp_own_mask(qbox[None], boxes, box, uni)[0]
                                & geometry.intersects(qbox[None], boxes))
        )(mb, tb)
        return jnp.sum(hits & tlive[:, None], dtype=jnp.int32)

    return jax.vmap(per_query)(qboxes, routed, live), fanout > max_fanout
