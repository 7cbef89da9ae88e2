"""Blocked range-probe kernel (TPU Pallas): query boxes vs member tiles.

The serving hot spot: a (Q, 4) batch of range-query boxes is tested
against member tiles of the ``serve.engine`` staging format.  Like
``mbr_join`` this is a VPU problem — (BQ, 128) blocks of boolean
closed-box compares from rank-1 broadcasts, queries on the sublane axis
and members on the 128-lane axis.

One kernel serves every probe family; the callers in ``ops`` differ
only in how many member rows they stream:

- **dense** (``R == 1``): every query sees the same ``n_cols == T``
  tiles — the all-tile oracle sweep.
- **gathered** (``R == Q``): row j of the member stack holds query j's
  *own* ``n_cols == F`` candidate tiles (router output), so work drops
  from O(Q·T·cap) to O(Q·F·cap) — the partition-pruning win the
  paper's fan-out metric predicts.

Grid: ``(query block i, tile column t, member block k)``.  Member block
k covers ``G`` consecutive 128-member chunks (``G`` divides the chunk
count, at most ``MAX_CHUNKS_PER_BLOCK``), so fast memory per grid cell
is bounded by ``BQ·G·128`` members however large ``cap`` grows.  The
count form accumulates per-lane partial counts across k in its
resident (BQ, 128) output block; the wrapper sums the lanes.  The mask
form writes its (BQ, G·128) hit block.

Local index (``cboxes``, LocationSpark's intra-partition layer): each
128-member chunk carries one MBR ("chunk box").  A member hit counts
only if its query also hits the chunk box; chunks no query of the block
hits skip the member compare entirely (``pl.when``).  The per-query
test is a (BQ, 1) column select that turns a missing query's ``xmin``
into NaN, which compares false against everything — answers equal the
``ref`` chunk-masked oracles bit for bit, whether or not the chunk
boxes bound their members.

Tombstones (keyword-only ``alive``, the ingest engine's per-slot mask):
a dead slot's member ``xmin`` reads as NaN, so it never hits; under
``cboxes`` a chunk with no live slot is skipped too.

Padding contract (same as mbr_join): callers pad query slots, member
slots, and absent candidate tiles with *inverted* sentinel boxes
(xmin > xmax), which intersect nothing; all-sentinel chunks carry
inverted chunk boxes and are always skipped.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BQ = 128
CHUNK = 128  # members summarised per chunk box (the VPU lane width)
MAX_CHUNKS_PER_BLOCK = 8


def chunks_per_block(n_chunks: int) -> int:
    """Largest divisor of ``n_chunks`` that is at most
    ``MAX_CHUNKS_PER_BLOCK`` — the member block is G whole chunks."""
    return max(g for g in range(1, MAX_CHUNKS_PER_BLOCK + 1)
               if n_chunks % g == 0)


def _probe_kernel(*refs, g: int, skip: bool, masked: bool, mask: bool):
    q_ref, t_ref, *rest = refs
    cb_ref = rest.pop(0) if skip else None
    a_ref = rest.pop(0) if masked else None
    (out_ref,) = rest

    if not mask:
        @pl.when(pl.program_id(2) == 0)
        def _():
            out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    qx0, qy0 = q_ref[:, 0:1], q_ref[:, 1:2]        # (BQ, 1)
    qx1, qy1 = q_ref[:, 2:3], q_ref[:, 3:4]
    for j in range(g):
        sl = slice(j * CHUNK, (j + 1) * CHUNK)
        go = None
        qx0_j = qx0
        if skip:
            c = 4 * j
            cx0, cy0 = cb_ref[0, :, c:c + 1], cb_ref[0, :, c + 1:c + 2]
            cx1, cy1 = cb_ref[0, :, c + 2:c + 3], cb_ref[0, :, c + 3:c + 4]
            live = (qx0 <= cx1) & (cx0 <= qx1) & (qy0 <= cy1) & (cy0 <= qy1)
            qx0_j = jnp.where(live, qx0, jnp.nan)   # missed chunk: no hit
            go = jnp.max(live.astype(jnp.float32)) > 0.0
        sx0 = t_ref[0, :, sl]                      # (1 | BQ, CHUNK)
        if masked:
            slot_live = a_ref[:, sl]
            sx0 = jnp.where(slot_live, sx0, jnp.nan)   # dead slot: no hit
            if skip:
                go = go & (jnp.max(slot_live.astype(jnp.float32)) > 0.0)

        def hits(qx0_j=qx0_j, sx0=sx0, sl=sl):
            return ((qx0_j <= t_ref[2, :, sl]) & (sx0 <= qx1)
                    & (qy0 <= t_ref[3, :, sl]) & (t_ref[1, :, sl] <= qy1))

        if mask:
            if go is None:
                out_ref[:, sl] = hits()
            else:
                @pl.when(go)
                def _(hits=hits, sl=sl):
                    out_ref[:, sl] = hits()

                @pl.when(jnp.logical_not(go))
                def _(sl=sl):
                    out_ref[:, sl] = jnp.zeros((out_ref.shape[0], CHUNK),
                                               out_ref.dtype)
        elif go is None:
            out_ref[...] += hits().astype(jnp.int32)
        else:
            @pl.when(go)
            def _(hits=hits):
                out_ref[...] += hits().astype(jnp.int32)


def probe_pallas(qboxes: jax.Array, tiles: jax.Array,
                 cboxes: jax.Array | None = None, *,
                 alive: jax.Array | None = None, mask: bool = False,
                 bq: int = DEFAULT_BQ, interpret: bool = False) -> jax.Array:
    """Closed-box hits of query boxes against member tiles.

    qboxes: (Q, 4) f32, ``Q % bq == 0``.  tiles: (4, R, n_cols, cap)
    f32 component-major member boxes, ``cap % CHUNK == 0``; ``R == 1``
    shares the tiles with every query (dense), ``R == Q`` gives query j
    its own row (gathered).  cboxes: (R, n_cols, cap // CHUNK, 4) chunk
    boxes or None (no local index).  alive: (R, n_cols, cap) bool or
    None (all live).

    -> ``mask=False``: (Q, n_cols) int32 hit counts;
       ``mask=True``: (Q, n_cols, cap) bool hit table.
    """
    nq = qboxes.shape[0]
    _, r, n_cols, cap = tiles.shape
    n_chunks = cap // CHUNK
    g = chunks_per_block(n_chunks)
    nk = n_chunks // g
    blk = g * CHUNK
    gathered = r > 1
    skip, masked = cboxes is not None, alive is not None

    def rows(i):
        return i if gathered else 0

    args = [qboxes, tiles.reshape(4, r, n_cols * cap)]
    in_specs = [
        pl.BlockSpec((bq, 4), lambda i, t, k: (i, 0)),
        pl.BlockSpec((4, bq if gathered else 1, blk),
                     lambda i, t, k: (0, rows(i), t * nk + k)),
    ]
    if skip:
        # (R, n_cols, C, 4) -> (n_cols·nk, R, 4G): member block k of
        # column t reads its G chunk boxes as one (rows, 4G) slab
        cb = cboxes.reshape(r, n_cols * nk, 4 * g)
        args.append(jnp.swapaxes(cb, 0, 1))
        in_specs.append(pl.BlockSpec((1, bq if gathered else 1, 4 * g),
                                     lambda i, t, k: (t * nk + k, rows(i), 0)))
    if masked:
        args.append(alive.reshape(r, n_cols * cap))
        in_specs.append(pl.BlockSpec((bq if gathered else 1, blk),
                                     lambda i, t, k: (rows(i), t * nk + k)))
    if mask:
        out_spec = pl.BlockSpec((bq, blk), lambda i, t, k: (i, t * nk + k))
        out_shape = jax.ShapeDtypeStruct((nq, n_cols * cap), jnp.bool_)
    else:
        out_spec = pl.BlockSpec((bq, CHUNK), lambda i, t, k: (i, t))
        out_shape = jax.ShapeDtypeStruct((nq, n_cols * CHUNK), jnp.int32)
    out = pl.pallas_call(
        functools.partial(_probe_kernel, g=g, skip=skip, masked=masked,
                          mask=mask),
        grid=(nq // bq, n_cols, nk),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="range_probe",
    )(*args)
    if mask:
        return out.reshape(nq, n_cols, cap)
    return jnp.sum(out.reshape(nq, n_cols, CHUNK), axis=2)
