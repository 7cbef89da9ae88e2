"""Public jit'd wrappers for the range_probe kernels.

Handles padding to block multiples (with never-intersecting sentinel
boxes) and the component-major layouts the kernel wants.  Off the TPU
(the CPU test path) ``interpret=None`` selects interpret mode or the
fused-jnp ``ref`` twin by backend; on the TPU every wrapper runs the
compiled kernel.  The natural caller is ``repro.serve.engine``, whose
staged layouts are already sentinel-padded and 128-aligned.

Candidate-list contract (``gathered_*``): ``cand`` is (Q, F) int32 tile
indices from ``repro.serve.router`` — entries in [0, T) are real tiles,
``-1`` marks padding slots and is remapped to an all-sentinel tile, so
padded candidates contribute exactly zero hits and no validity mask is
needed downstream.

Local-index contract (``*_skip``): ``cboxes`` is the staging's
``(T, C, 4)`` chunk-box summary (``C == ceil(cap / CHUNK)``, chunk c
bounding member slots ``[c*CHUNK, (c+1)*CHUNK)``; all-sentinel chunks
carry inverted boxes).  Answers equal the unindexed variants whenever
the chunk boxes bound their members; on TPU dead chunks are skipped,
off-TPU the fused jnp path masks per-chunk partials (same O(1/CHUNK)
bookkeeping cost, same bits).

Tombstone contract (keyword-only ``alive``): an optional (T, cap) bool
per-slot alive mask — a hit counts only if its member slot is alive.
Wrappers pad it with False (dead) and gather it alongside the member
boxes, so padded slots and padded candidates stay inert.  ``alive=None``
is the all-live fast path, bit-identical to an all-``True`` mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.geometry import SENTINEL_BOX
from . import kernel
from .kernel import CHUNK  # re-exported: staging chunks on this

_SENTINEL = jnp.array(SENTINEL_BOX, jnp.float32)


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pad_queries(qboxes: jax.Array, bq: int) -> jax.Array:
    """(Q, 4) -> (Q_pad, 4) f32 with sentinel padding to a block multiple."""
    qboxes = qboxes.astype(jnp.float32)
    pad = (-qboxes.shape[0]) % bq
    if pad:
        qboxes = jnp.concatenate(
            [qboxes, jnp.broadcast_to(_SENTINEL, (pad, 4))], axis=0)
    return qboxes


def _pad_cap(tiles: jax.Array) -> jax.Array:
    """(T, cap, 4) -> (T, cap_pad, 4) f32 with sentinel member padding."""
    tiles = tiles.astype(jnp.float32)
    pad = (-tiles.shape[1]) % CHUNK
    if pad:
        tiles = jnp.concatenate(
            [tiles, jnp.broadcast_to(_SENTINEL, (tiles.shape[0], pad, 4))],
            axis=1)
    return tiles


def _pad_alive(alive: jax.Array) -> jax.Array:
    """(T, cap) bool -> (T, cap_pad) with False (dead) padding."""
    cap = alive.shape[1]
    pad = (-cap) % CHUNK
    if pad:
        alive = jnp.pad(alive, ((0, 0), (0, pad)))
    return alive


def _append_pad_row(table: jax.Array, pad_value) -> tuple[jax.Array, int]:
    """Append one row of ``pad_value`` to ``table``'s leading axis; the
    single definition of the '-1 candidate -> pad row' remap target.
    -> ``(table_p[T+1, ...], t)`` where remapping is
    ``where(cand >= 0, cand, t)``."""
    t = table.shape[0]
    row = jnp.broadcast_to(jnp.asarray(pad_value, table.dtype),
                           (1,) + table.shape[1:])
    return jnp.concatenate([table, row], axis=0), t


def _use_ref(interpret: bool | None) -> bool:
    """``interpret=None`` off the TPU runs the fused-jnp ``ref`` executor
    (the gathered layouts' interpret-mode kernel is slow on CPU); an
    explicit ``interpret`` always runs the Pallas kernel."""
    return interpret is None and _interpret_default()


def _dense(qboxes, tiles, cboxes, alive, bq, interpret, mask):
    """All-tile probe through the kernel: -> (Q, T) counts or
    (Q, T, cap) mask."""
    q, cap = qboxes.shape[0], tiles.shape[1]
    members = jnp.moveaxis(_pad_cap(tiles), 2, 0)[:, None]  # (4,1,T,cap_p)
    out = kernel.probe_pallas(
        _pad_queries(qboxes, bq), members,
        None if cboxes is None else cboxes.astype(jnp.float32)[None],
        alive=None if alive is None else _pad_alive(alive)[None],
        mask=mask, bq=bq, interpret=interpret)
    return out[:q, :, :cap] if mask else out[:q]


def _gathered(qboxes, tiles, cboxes, cand, alive, bq, interpret, mask):
    """Candidate-tile probe through the kernel: pad queries to a block
    multiple, remap -1 candidates to an appended all-sentinel (all-dead)
    tile, gather each query's candidate stack -> (Q, F) counts or
    (Q, F, cap) mask."""
    q, cap = qboxes.shape[0], tiles.shape[1]
    tiles_p, t = _append_pad_row(_pad_cap(tiles), _SENTINEL)
    cidx = jnp.where(cand >= 0, cand, t)
    pad = (-q) % bq
    if pad:
        cidx = jnp.concatenate(
            [cidx, jnp.full((pad, cand.shape[1]), t, cidx.dtype)], axis=0)
    members = jnp.moveaxis(tiles_p, 2, 0)[:, cidx]   # (4, Q_pad, F, cap_p)
    cb = None
    if cboxes is not None:
        cb = _append_pad_row(cboxes.astype(jnp.float32), _SENTINEL)[0][cidx]
    a = None
    if alive is not None:
        a = _append_pad_row(_pad_alive(alive), False)[0][cidx]
    out = kernel.probe_pallas(_pad_queries(qboxes, bq), members, cb,
                              alive=a, mask=mask, bq=bq,
                              interpret=interpret)
    return out[:q, :, :cap] if mask else out[:q]


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def probe_counts(qboxes: jax.Array, tiles: jax.Array,
                 bq: int = kernel.DEFAULT_BQ,
                 interpret: bool | None = None, *,
                 alive: jax.Array | None = None) -> jax.Array:
    """Per-(query, tile) hit counts.

    qboxes: (Q, 4), tiles: (T, cap, 4) sentinel-padded member boxes
    -> (Q, T) int32.  ``alive``: (T, cap) bool — dead slots never count.
    ``interpret=None`` runs the kernel in interpret mode off the TPU.
    """
    if interpret is None:
        interpret = _interpret_default()
    return _dense(qboxes, tiles, None, alive, bq, interpret, mask=False)


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def probe_mask(qboxes: jax.Array, tiles: jax.Array,
               bq: int = kernel.DEFAULT_BQ,
               interpret: bool | None = None, *,
               alive: jax.Array | None = None) -> jax.Array:
    """Full hit table for id extraction.

    qboxes: (Q, 4), tiles: (T, cap, 4) -> (Q, T, cap) bool (un-padded
    view).  O(Q·T·cap) output — the count path is the throughput path.
    """
    if interpret is None:
        interpret = _interpret_default()
    return _dense(qboxes, tiles, None, alive, bq, interpret, mask=True)


# reprolint: disable=kernel-twin-parity -- pure data mover: gathers raw
# member boxes for downstream twins; tombstones are enforced where the
# hits are computed, via the parallel gathered_alive mask
def gathered_rows(tiles: jax.Array, cand: jax.Array) -> jax.Array:
    """Row-major candidate gather: (T, cap, 4) x (Q, F) -> (Q, F, cap, 4)
    with -1 candidates remapped to an appended all-sentinel tile (the
    shared ``SENTINEL_BOX`` contract).  XLA fuses this into a consuming
    compare, so nothing materialises — the fast non-TPU executor for
    the gathered probe, also reused by ``query.knn`` for candidate
    member boxes."""
    tiles_p, t = _append_pad_row(tiles.astype(jnp.float32), _SENTINEL)
    return tiles_p[jnp.where(cand >= 0, cand, t)]


def gathered_ids(ids: jax.Array, cand: jax.Array) -> jax.Array:
    """Candidate gather of member ids: (T, cap) int32 x (Q, F) ->
    (Q, F, cap) with -1 candidates remapped to an appended all ``-1``
    row — the id-side companion of ``gathered_rows``, so padded
    candidates read as padding slots downstream."""
    ids_p, t = _append_pad_row(ids, -1)
    return ids_p[jnp.where(cand >= 0, cand, t)]


def gathered_alive(alive: jax.Array, cand: jax.Array) -> jax.Array:
    """Candidate gather of the alive mask: (T, cap) bool x (Q, F) ->
    (Q, F, cap) with -1 candidates remapped to an appended all-``False``
    (dead) row — the tombstone companion of ``gathered_rows``, so padded
    candidates never answer."""
    alive_p, t = _append_pad_row(alive, False)
    return alive_p[jnp.where(cand >= 0, cand, t)]


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def gathered_counts(qboxes: jax.Array, tiles: jax.Array, cand: jax.Array,
                    bq: int = kernel.DEFAULT_BQ,
                    interpret: bool | None = None, *,
                    alive: jax.Array | None = None) -> jax.Array:
    """Routed probe: per-(query, candidate) hit counts.

    qboxes: (Q, 4); tiles: (T, cap, 4) sentinel-padded member boxes;
    cand: (Q, F) int32 candidate tile indices (-1 = padding)
    -> (Q, F) int32.  O(Q·F·cap) work vs the dense O(Q·T·cap).

    ``interpret=None`` picks the backend's executor: the Pallas kernel
    on TPU, the fused-jnp gather+compare off it.  Pass
    ``interpret=True`` to force the interpret-mode kernel (validation
    path); results are identical either way.
    """
    if _use_ref(interpret):
        from . import ref
        return ref.gathered_counts(
            qboxes.astype(jnp.float32), gathered_rows(tiles, cand),
            None if alive is None else gathered_alive(alive, cand))
    return _gathered(qboxes, tiles, None, cand, alive, bq, bool(interpret),
                     mask=False)


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def gathered_mask(qboxes: jax.Array, tiles: jax.Array, cand: jax.Array,
                  bq: int = kernel.DEFAULT_BQ,
                  interpret: bool | None = None, *,
                  alive: jax.Array | None = None) -> jax.Array:
    """Routed probe, full hit table over candidate tiles only.

    qboxes: (Q, 4); tiles: (T, cap, 4); cand: (Q, F) int32 (-1 padding)
    -> (Q, F, cap) bool (un-padded view); slot (j, f, c) is True iff
    query j intersects member c of its f-th candidate tile.  Executor
    selection as in ``gathered_counts``.
    """
    if _use_ref(interpret):
        from . import ref
        return ref.gathered_mask(
            qboxes.astype(jnp.float32), gathered_rows(tiles, cand),
            None if alive is None else gathered_alive(alive, cand))
    return _gathered(qboxes, tiles, None, cand, alive, bq, bool(interpret),
                     mask=True)


# --------------------------------------------------------------------------
# chunk-skipping (local-index) variants
# --------------------------------------------------------------------------

def gathered_chunk_boxes(cboxes: jax.Array, cand: jax.Array) -> jax.Array:
    """Candidate gather of chunk boxes: (T, C, 4) x (Q, F) ->
    (Q, F, C, 4) with -1 candidates remapped to an appended all-sentinel
    chunk row — the chunk-box companion of ``gathered_rows``, so padded
    candidates' chunks never test live."""
    cb_p, t = _append_pad_row(cboxes.astype(jnp.float32), _SENTINEL)
    return cb_p[jnp.where(cand >= 0, cand, t)]


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def probe_counts_skip(qboxes: jax.Array, tiles: jax.Array,
                      cboxes: jax.Array, bq: int = kernel.DEFAULT_BQ,
                      interpret: bool | None = None, *,
                      alive: jax.Array | None = None) -> jax.Array:
    """Dense per-(query, tile) hit counts with chunk skipping.

    qboxes: (Q, 4); tiles: (T, cap, 4); cboxes: (T, C, 4) chunk boxes
    (``C == ceil(cap / CHUNK)``) -> (Q, T) int32, equal to
    ``probe_counts`` whenever each chunk box bounds the members of
    *this* ``tiles`` array in its slot range.  NB the staging's
    ``chunk_boxes`` bound **canonical** members only — pair them with
    ``canon_tiles``; probing the full member tiles needs chunk boxes
    built over the full tiles.  Executor selection as in
    ``gathered_counts``: the Pallas skip kernel on TPU (or
    ``interpret=True``), the fused chunk-masked jnp path off-TPU.
    """
    if _use_ref(interpret):
        from . import ref
        return ref.probe_counts_skip(qboxes.astype(jnp.float32),
                                     tiles.astype(jnp.float32),
                                     cboxes.astype(jnp.float32), alive)
    return _dense(qboxes, tiles, cboxes, alive, bq, bool(interpret),
                  mask=False)


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def probe_mask_skip(qboxes: jax.Array, tiles: jax.Array,
                    cboxes: jax.Array, bq: int = kernel.DEFAULT_BQ,
                    interpret: bool | None = None, *,
                    alive: jax.Array | None = None) -> jax.Array:
    """Dense hit table with chunk skipping: -> (Q, T, cap) bool
    (un-padded view); same chunk-box contract (boxes must bound the
    probed ``tiles`` — staged boxes pair with ``canon_tiles``) and
    executor selection as ``probe_counts_skip``."""
    if _use_ref(interpret):
        from . import ref
        return jnp.swapaxes(
            ref.probe_mask_skip(qboxes.astype(jnp.float32),
                                tiles.astype(jnp.float32),
                                cboxes.astype(jnp.float32), alive), 0, 1)
    return _dense(qboxes, tiles, cboxes, alive, bq, bool(interpret),
                  mask=True)


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def gathered_counts_skip(qboxes: jax.Array, tiles: jax.Array,
                         cboxes: jax.Array, cand: jax.Array,
                         bq: int = kernel.DEFAULT_BQ,
                         interpret: bool | None = None, *,
                         alive: jax.Array | None = None) -> jax.Array:
    """Routed per-(query, candidate) hit counts with chunk skipping.

    qboxes: (Q, 4); tiles: (T, cap, 4); cboxes: (T, C, 4); cand:
    (Q, F) int32 (-1 padding) -> (Q, F) int32, equal to
    ``gathered_counts`` whenever the chunk boxes bound their members —
    the serving hot path's local-index executor.
    """
    if _use_ref(interpret):
        from . import ref
        return ref.gathered_counts_skip(
            qboxes.astype(jnp.float32), gathered_rows(tiles, cand),
            gathered_chunk_boxes(cboxes, cand),
            None if alive is None else gathered_alive(alive, cand))
    return _gathered(qboxes, tiles, cboxes, cand, alive, bq,
                     bool(interpret), mask=False)


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def gathered_mask_skip(qboxes: jax.Array, tiles: jax.Array,
                       cboxes: jax.Array, cand: jax.Array,
                       bq: int = kernel.DEFAULT_BQ,
                       interpret: bool | None = None, *,
                       alive: jax.Array | None = None) -> jax.Array:
    """Routed hit table with chunk skipping: -> (Q, F, cap) bool
    (un-padded view); executor selection as in ``gathered_counts_skip``."""
    if _use_ref(interpret):
        from . import ref
        return ref.gathered_mask_skip(
            qboxes.astype(jnp.float32), gathered_rows(tiles, cand),
            gathered_chunk_boxes(cboxes, cand),
            None if alive is None else gathered_alive(alive, cand))
    return _gathered(qboxes, tiles, cboxes, cand, alive, bq,
                     bool(interpret), mask=True)


@jax.jit
def chunk_skip_rate(qboxes: jax.Array, cboxes: jax.Array,
                    cand: jax.Array) -> jax.Array:
    """Fraction of (query, live candidate) chunk probes the local index
    skips: chunks whose box the query misses, over all chunks of all
    non-padding candidates.  All-sentinel chunks (pure padding past a
    tile's canonical members) count as skipped — an unindexed probe
    would have swept them.  -> () f32 in [0, 1].
    """
    from . import ref
    live_cand = cand >= 0                                   # (Q, F)
    hit = ref.gathered_chunk_hits(qboxes.astype(jnp.float32),
                                  gathered_chunk_boxes(cboxes, cand))
    total = jnp.sum(live_cand) * cboxes.shape[1]
    skipped = jnp.sum(~hit & live_cand[..., None])
    return skipped / jnp.maximum(total, 1)
