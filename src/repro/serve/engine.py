"""The batched spatial query server (stage once, serve a moving dataset).

LocationSpark's architecture in SPMD form: a dataset is staged under
any of the six layouts — MASJ assignment into padded ``(T, cap, 4)``
member tiles plus a canonical-copy mark so selection queries dedup for
free (see ``query.range``) — then streams of query batches are
answered by a jitted step:

  route   — the global index maps the batch to partitions, yielding the
            per-query fan-out metric *and* a fixed-width ``(Q, F)``
            candidate-tile index over the layout's canonical probe
            boxes (``router.candidate_range`` / ``candidate_knn``),
  pack    — queries are LPT-packed onto devices with routed fan-out as
            the cost (the join engine's straggler story, applied to the
            query side: a batch of hotspot queries must not serialise
            on one device),
  probe   — the ``TileLayout`` executes the batch against its
            placement: candidate tiles only via the gathered
            ``range_probe`` Pallas kernel, with the intra-tile local
            index predicating dead chunks away,
  gather  — results come back query-sharded and are unpermuted.

How the server serves is one frozen value, ``ServeConfig``
(``serve.config``): data placement (``replicated`` | ``sharded``),
default probe (``pruned`` | the ``dense`` all-tile oracle), local-index
mode (``off`` | ``x`` | ``hilbert``), chunk granularity, and the
capacity/slack policy.  The server itself is written once against the
``TileLayout`` protocol (``serve.layout``) — there is no placement
branch anywhere in the query paths; ``ReplicatedTiles`` and
``ShardedTiles`` implement the same contract (the latter through the
owner-routed ``all_to_all`` exchange, ``serve.exchange``).

The dataset *moves*: ``append(mbrs)`` streams new objects into the
slack slots staging reserved (``config.slack``), scattering only the
touched ``(tile, slot)`` cells to device — append cost tracks the
batch, not the layout; a tile overflow re-stages the layout at a grown
capacity (re-balancing owners under sharding) and resets the
``WidthPolicy``.  ``delete(ids)`` tombstones objects by flipping their
slots' alive bits (``update`` moves them), and the ``ServeConfig``
compaction policy reclaims dead slots — tile-locally past
``compact_dead_frac``, by full re-stage past ``restage_dead_frac``.
Answers after any ingest sequence are bit-identical to re-staging the
live set from scratch — and to the dense oracle — because every answer
is a function of the live canonical membership sets alone.

Exactness of the pruned path is never assumed: range candidate lists
are sized from the batch's true max fan-out, and kNN flags any query
whose refinement radius reaches a tile outside its frontier, which the
server retries with a doubled frontier until exact (worst case the
frontier is every tile — the dense sweep).  Converged candidate widths
are remembered per query kind (``WidthPolicy``), so steady query
streams pay recompiles and kNN widening ladders once.

Host spans (``jax.profiler.TraceAnnotation``, recorded only while a
profiler trace is active) mark each batch's host phases: ``serve.route``
(routing: overlap, host fold, width ratchet, candidates; for kNN the
converged frontier), its child ``serve.heat`` (folding the batch into
the heat tracker), ``serve.fanout_stats`` (the reported fan-out metric)
and ``serve.probe`` (dispatching the executor; the device runs on past
it; ``attempt`` numbers a kNN batch's frontier widenings).

Single-process use passes ``mesh=None`` and gets the same jitted maths
without the collective plumbing (sharded placement then runs the
exchange in vmap simulation — same answers, one device).

For serving streams of *single* requests (an online workload rather
than pre-formed batches), ``serve.frontend`` puts an async request
plane in front of this server: admission control, per-tenant fairness,
and deadline-or-full batch forming onto a fixed compiled-shape ladder.
"""
from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh

from ..core.partition import api
from ..core.partition.assign import round_up
from ..kernels.range_probe import ops as rops
from ..query import knn as knn_mod
from . import router
from .config import ServeConfig
from .layout import (  # noqa: F401  (re-exports: the staging surface)
    HeatSharded,
    ReplicatedTiles,
    ShardedLayout,
    ShardedTiles,
    StagedLayout,
    TileLayout,
    build_tiles,
    pack_queries,
    shard_staged,
    stage_tiles,
)

log = logging.getLogger(__name__)


def _f_width(fanout_max: int, t: int) -> int:
    """Candidate-list width: max batch fan-out rounded up to 8 (bounds
    jit recompiles to one per width bucket), capped at the tile count."""
    return min(max(t, 1), round_up(max(fanout_max, 1), 8))


class WidthPolicy:
    """Adaptive candidate-width cache (ROADMAP: adaptive ``f_max``).

    One policy per server, hence per (layout, dataset); keys are query
    kinds (``"range"`` or ``("knn", k, max_cand)``).  Widths only move
    up (``observe`` keeps the max — wider is always exact), and two
    lookup flavours serve the two consumers:

    - ``at_least(key, floor)`` — range batches: the answer must cover
      this batch's true fan-out, so return ``max(cached, floor)``; a
      narrow batch after a wide one reuses the already-compiled wider
      step instead of recompiling.
    - ``start(key, default)`` — kNN batches: any width is *correct*
      (the frontier-miss check widens until exact), so start straight
      from the converged width of earlier batches and skip their
      widening ladder; fall back to the density ``default`` cold.

    Cached widths are clamped to ``cap`` (the server passes its
    ``t_live`` — no candidate list can usefully exceed the live tile
    count), so one pathological batch can never inflate later batches'
    gather width and memory past the layout itself; ``reset()`` drops
    the cache entirely when a stream's width profile changes — the
    server hooks it on every streaming re-stage, where the layout the
    widths converged against no longer exists.

    ``hits``/``misses`` count cache effectiveness; ``seed`` force-sets
    a width unclamped (tests use it to exercise the widen-and-retry
    path).
    """

    def __init__(self, cap: int | None = None):
        self.cap = cap
        self._w: dict = {}
        self.hits = 0
        self.misses = 0

    def _clamp(self, w: int) -> int:
        return w if self.cap is None else min(w, self.cap)

    def at_least(self, key, floor: int) -> int:
        w = self._w.get(key)
        if w is not None and w >= floor:
            self.hits += 1
            return w
        self.misses += 1
        return floor

    def start(self, key, default: int) -> int:
        w = self._w.get(key)
        if w is not None:
            self.hits += 1
            return w
        self.misses += 1
        return default

    def observe(self, key, width: int) -> None:
        self._w[key] = self._clamp(max(self._w.get(key, 0), width))

    def reset(self) -> None:
        """Forget every cached width (the next batch of each kind pays
        one recompile / widening ladder again, at its natural width)."""
        self._w.clear()

    def seed(self, key, width: int) -> None:
        self._w[key] = width


class SpatialServer:
    """Stage once, then serve batched range / kNN queries — and keep
    serving as the dataset grows.

    ``config`` (a frozen ``ServeConfig``) picks the placement
    (``replicated`` | ``sharded``), the default probe (``pruned``
    routed candidates | the ``dense`` all-tile oracle — also a per-call
    ``pruned=`` override), the intra-tile local index (``off`` | ``x``
    | ``hilbert``), chunk granularity, and the capacity/slack policy
    for streaming ``append``.  ``mesh=None`` serves in-process; with a
    mesh every batch runs as an SPMD step over ``mesh[config.axis]``.

    The server is placement-agnostic: it routes, packs, and enforces
    exactness (the kNN widen-and-retry ladder), delegating execution to
    its ``TileLayout`` (``self.tiles``).  Answers are bit-identical
    across placements, probe modes, and local-index modes on all six
    layouts (tested), including after any sequence of ``append`` calls.
    """

    def __init__(self, parts: api.Partitioning, mbrs: jax.Array,
                 config: ServeConfig | None = None, *,
                 mesh: Mesh | None = None, method: str | None = None):
        self.config = config = config if config is not None else ServeConfig()
        self.parts = parts
        self.mesh = mesh
        self.tiles: TileLayout = build_tiles(parts, mbrs, config, mesh)
        self.stats = self.tiles.stats      # one dict, shared — appends
        self.stats["method"] = method      # mutate it in place
        self.widths = WidthPolicy(cap=self.stats["t_live"])
        # query-heat signals for heat-aware placement: every routed
        # batch's candidate lists fold in (O(Q·F) numpy, no device
        # work); ``rebalance()`` turns them into a placement plan
        self.heat = router.HeatTracker(self.stats["t"],
                                       decay=config.policy.heat_decay)
        self._batches_since_rebalance = 0

    @classmethod
    def from_method(cls, method: str, mbrs: jax.Array, payload: int,
                    config: ServeConfig | None = None, *,
                    mesh: Mesh | None = None) -> "SpatialServer":
        """Partition ``mbrs`` with ``method`` at ``payload`` and serve.

        Everything after ``payload`` — ``config`` included — reaches
        the constructor verbatim, so staging knobs like
        ``ServeConfig.capacity`` are honoured here exactly as on the
        direct path.
        """
        parts = api.partition(method, mbrs, payload)
        return cls(parts, mbrs, config, mesh=mesh, method=method)

    # -- shared accessors -------------------------------------------------

    @property
    def probe_boxes(self) -> jax.Array:
        return self.tiles.probe_boxes

    @property
    def uni(self) -> jax.Array:
        return self.tiles.uni

    @property
    def chunk_boxes(self) -> jax.Array | None:
        """The (T, C, 4) global local index (None when unindexed)."""
        return self.tiles.chunk_boxes

    @property
    def layout(self) -> StagedLayout | None:
        """The replicated staging (None under ``placement='sharded'``)."""
        return getattr(self.tiles, "staged", None)

    @property
    def slayout(self) -> ShardedLayout | None:
        """The sharded staging (None under ``placement='replicated'``)."""
        return getattr(self.tiles, "slayout", None)

    @property
    def shards(self) -> int:
        return self.tiles.shards

    @property
    def n_devices(self) -> int:
        return self.tiles.n_devices

    @property
    def _oracle_np(self):
        return self.tiles.oracle_np

    def chunk_skip_rate(self, qboxes: jax.Array) -> float:
        """Measured local-index effectiveness for one batch: the
        fraction of per-candidate 128-member chunks whose box the query
        misses (work the ``*_skip`` kernels drop).  0.0 when staged
        with ``local_index="off"``.  Pure measurement — does not touch
        the width cache."""
        if self.chunk_boxes is None:
            return 0.0
        hit = router.probe_overlap(self.probe_boxes, qboxes)
        # reprolint: disable=host-sync -- routing is host-side by design:
        # one fold of the overlap matrix feeds the width ratchet + packing
        pf = np.asarray(jnp.sum(hit, axis=1, dtype=jnp.int32))
        f = _f_width(int(pf.max(initial=0)), self.stats["t_live"])
        cand, _, _ = router.candidates_from_overlap(hit, f)
        return float(rops.chunk_skip_rate(qboxes, self.chunk_boxes, cand))

    def resident_tile_bytes(self) -> int:
        """Per-device bytes of device-resident staged member data —
        the O(N) (replicated) vs O(N/D) (sharded) axis the benchmarks
        report."""
        return self.tiles.resident_tile_bytes()

    # -- streaming --------------------------------------------------------

    def append(self, mbrs) -> dict:
        """Stream new objects into the served layout.

        mbrs: (M, 4) f32 MBRs; ids continue the running numbering.
        Inserts into each tile's reserved slack (probe/chunk boxes
        refresh incrementally, compiled steps stay warm); a tile
        overflow re-stages the layout at a grown capacity — owners
        re-balance under sharding — and resets the width cache, whose
        converged widths described the old staging.  Returns the append
        report (``appended``, ``restaged``, ``n``, ``cap``,
        ``free_slots_min``).  Answers after any append sequence are
        bit-identical to a from-scratch staging of the full dataset.
        """
        report = self.tiles.append(mbrs)
        self.widths.cap = self.stats["t_live"]
        if report["restaged"]:
            self.widths.reset()
        return report

    def delete(self, ids) -> dict:
        """Tombstone objects by id: their slots' alive bits flip off (a
        few-byte scatter — member boxes stay put as routing supersets)
        and every query path stops counting them.  Unknown, repeated,
        or already-deleted ids raise ``ValueError`` naming them.  May
        trigger the config's compaction policy (``compact_dead_frac`` /
        ``restage_dead_frac``); the report carries ``deleted``, ``n``,
        ``dead_frac``, ``compacted_tiles``, ``restaged``.
        """
        return self._after_maintenance(self.tiles.delete(ids))

    def update(self, ids, mbrs) -> dict:
        """Move objects: tombstone each id's current canonical slot and
        re-insert its new MBR under the same id (delete + append in one
        scatter).  A tile overflow re-stages like ``append``; otherwise
        the compaction policy applies as in ``delete``.
        """
        return self._after_maintenance(self.tiles.update(ids, mbrs))

    def compact(self) -> dict:
        """Force tile-local compaction of every tile holding dead
        slots, regardless of the config thresholds (re-sorts survivors,
        tightens probe/chunk boxes, zeroes the dead counts)."""
        return self._after_maintenance(self.tiles.compact())

    def _after_maintenance(self, report: dict) -> dict:
        """Shared post-ingest bookkeeping: live-tile count may move
        (compaction empties tiles, re-stage rebuilds them), and a
        re-stage invalidates the width cache's converged widths."""
        self.widths.cap = self.stats["t_live"]
        if report.get("restaged"):
            self.widths.reset()
        return report

    # -- heat-aware placement ---------------------------------------------

    def rebalance(self) -> dict:
        """Apply a heat-aware placement plan under traffic.

        Snapshots the heat tracker and hands it to the layout: owners
        re-plan co-locating co-occurring tiles (move-minimised from the
        current plan) and, under ``placement="heat"``, the hottest
        ``config.policy.replicate_top`` tiles refresh their replicas.
        Tile contents never move logically — answers are bit-identical
        before and after — only the owner maps and shard scatter
        change.  No-op report under ``placement="replicated"``.
        """
        heat, cooc = self.heat.snapshot()
        report = self.tiles.rebalance(heat, cooc)
        self._batches_since_rebalance = 0
        return report

    def _observe(self, cand) -> None:
        """Fold one routed batch into the heat tracker; auto-rebalance
        every ``config.policy.rebalance_every`` observed batches."""
        with TraceAnnotation("serve.heat"):
            self.heat.observe(np.asarray(cand))
            self._batches_since_rebalance += 1
            every = self.config.policy.rebalance_every
            if every is not None and self._batches_since_rebalance >= every:
                self.rebalance()

    # -- routing helpers (host side, per batch) ---------------------------

    def _use_pruned(self, pruned: bool | None) -> bool:
        return (self.config.probe == "pruned") if pruned is None else pruned

    def _route_batch(self, qboxes: jax.Array):
        """Candidate-tile index for one range batch.  ``f_max`` covers
        the batch's true max probe fan-out — never truncating — and is
        ratcheted through the width cache so narrower follow-up batches
        reuse the compiled step.  Returns ``(cand[Q, F], costs[Q], F)``.
        """
        with TraceAnnotation("serve.route"):
            hit = router.probe_overlap(self.probe_boxes, qboxes)
            # reprolint: disable=host-sync -- routing is host-side by design:
            # one fold of the overlap matrix feeds the width ratchet + packing
            pf = np.asarray(jnp.sum(hit, axis=1, dtype=jnp.int32))
            floor = _f_width(int(pf.max(initial=0)), self.stats["t_live"])
            f = self.widths.at_least("range", floor)
            cand, _, _ = router.candidates_from_overlap(hit, f)
            self.widths.observe("range", f)
            self._observe(cand)
        return cand, pf.astype(np.float64), f

    def _fanout_stats(self, qboxes: jax.Array) -> dict:
        """The paper's reported metric: region fan-out from the global
        index (independent of the executor's probe-box routing)."""
        with TraceAnnotation("serve.fanout_stats"):
            _, fanout = router.route_range(self.parts, qboxes)
            fanout_np = np.asarray(fanout)
        return dict(fanout_mean=float(fanout_np.mean()),
                    fanout_max=int(fanout_np.max()))

    # -- queries ----------------------------------------------------------

    def range_counts(self, qboxes: jax.Array, pruned: bool | None = None):
        """Exact unique hit counts -> ``((Q,) int32, stats)``.

        stats carry the region fan-out metric, the packing skew, and
        ``mode``/``f_max`` describing the executor that ran.
        """
        stats = self._fanout_stats(qboxes)
        if self._use_pruned(pruned):
            cand, costs, f = self._route_batch(qboxes)
            with TraceAnnotation("serve.probe"):
                counts, xstats = self.tiles.range_counts(qboxes, cand, costs)
            stats.update(mode=self.tiles.mode, f_max=f, **xstats)
        else:
            with TraceAnnotation("serve.probe"):
                counts, xstats = self.tiles.dense_range_counts(qboxes)
            stats.update(mode="dense", **xstats)
        return counts, stats

    def range_ids(self, qboxes: jax.Array, max_hits: int = 1024,
                  pruned: bool | None = None):
        """Exact unique hit-id sets (ascending, -1 padded) + overflow
        -> ``(hit_ids[Q, max_hits], counts[Q], overflow[Q], stats)``."""
        stats = self._fanout_stats(qboxes)
        if self._use_pruned(pruned):
            cand, costs, f = self._route_batch(qboxes)
            with TraceAnnotation("serve.probe"):
                hit_ids, counts, overflow, xstats = self.tiles.range_ids(
                    qboxes, cand, costs, max_hits)
            stats.update(mode=self.tiles.mode, f_max=f, **xstats)
        else:
            with TraceAnnotation("serve.probe"):
                hit_ids, counts, overflow, xstats = \
                    self.tiles.dense_range_ids(qboxes, max_hits)
            stats.update(mode="dense", **xstats)
        return hit_ids, counts, overflow, stats

    def knn(self, pts: jax.Array, k: int, max_cand: int = 1024,
            pruned: bool | None = None):
        """Exact batched kNN -> ``(nn_ids[Q, k], nn_d2[Q, k],
        overflow[Q], stats)``; reported fan-out = MINDIST partitions a
        best-first search would visit given the answered kth distance.

        The pruned executor starts from a density-sized MINDIST
        frontier (or the width cache's converged start) and doubles it
        for any batch whose refinement radius reached an excluded tile
        — logged and counted in ``stats['retries']`` — so returned
        answers match the dense oracle exactly.
        """
        if self._use_pruned(pruned):
            nn_ids, nn_d2, overflow, mode_stats = self._knn_retry_loop(
                pts, k, max_cand)
            mode_stats = dict(mode=self.tiles.mode, **mode_stats)
        else:
            with TraceAnnotation("serve.probe"):
                nn_ids, nn_d2, overflow, xstats = self.tiles.dense_knn(
                    pts, k, max_cand)
            mode_stats = dict(mode="dense", **xstats)
        with TraceAnnotation("serve.fanout_stats"):
            fanout = knn_mod.knn_fanout(jnp.asarray(pts),
                                        jnp.asarray(nn_d2[:, -1]),
                                        self.parts.boxes, self.parts.valid)
            fanout_np = np.asarray(fanout)
        stats = dict(fanout_mean=float(fanout_np.mean()),
                     fanout_max=int(fanout_np.max()), **mode_stats)
        return nn_ids, nn_d2, overflow, stats

    def _knn_retry_loop(self, pts: jax.Array, k: int, max_cand: int):
        """The exactness-critical widen-and-retry ladder, written once
        against the protocol.

        ``tiles.knn_attempt(pts, k, max_cand, f)`` answers the batch
        with frontier width ``f``.  Any query whose √2-inflated
        refinement radius reaches its nearest excluded tile may have
        missed a true neighbour, so the frontier doubles (logged) until
        no query can miss or the frontier holds every live tile.
        Converged widths feed the width cache so a steady stream pays
        the ladder once.
        """
        t_live, n = self.stats["t_live"], self.stats["n"]
        wkey = ("knn", k, max_cand)
        f = self.widths.start(
            wkey, _f_width(4 * k * t_live // max(n, 1) + 3, t_live))
        retries = 0
        while True:
            with TraceAnnotation("serve.probe", attempt=retries):
                nn_ids, nn_d2, radius, overflow, excl, xstats = \
                    self.tiles.knn_attempt(pts, k, max_cand, f)
            miss = np.asarray(excl) <= np.asarray(radius) * np.sqrt(2.0)
            if not miss.any() or f >= t_live:
                break
            new_f = _f_width(2 * f, t_live)
            log.info("kNN frontier miss on %d/%d queries: widening "
                     "f_max %d -> %d (retry %d)",
                     int(miss.sum()), pts.shape[0], f, new_f, retries + 1)
            f = new_f
            retries += 1
        self.widths.observe(wkey, f)
        # heat sees the *converged* frontier — the tiles this batch
        # actually probed at its final width
        with TraceAnnotation("serve.route"):
            cand, _, _ = router.candidate_knn(self.probe_boxes, pts, f)
            self._observe(cand)
        overflow = np.asarray(overflow) | miss
        return (jnp.asarray(nn_ids), jnp.asarray(nn_d2),
                jnp.asarray(overflow),
                dict(f_max=f, retries=retries, **xstats))
