"""Blocked pairwise MBR-intersection kernel (TPU Pallas).

The per-tile spatial join tests every (r, s) MBR pair in a tile for
closed-box intersection.  On TPU this is a VPU problem: a (BR, BS) block
of boolean compares from rank-1 broadcasts.  Layout: coordinates arrive
as (4, N) — component-major — so the object axis is the 128-lane axis.

Two entry points:
- ``count``: grid cell (j, i) reduces its (BR, BS) block over the r
  axis and accumulates the (1, BS) per-s partial counts across the r
  blocks in its resident output block — O(M) output, used for
  selectivity/λ statistics and join counting.
- ``mask``:  writes the full boolean block — used for pair extraction on
  moderate tile sizes.

Padding contract: callers pad with *inverted* sentinel boxes
(xmin > xmax) which intersect nothing, so no separate validity mask is
streamed through VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BR = 256
DEFAULT_BS = 128


def _block_hits(r_ref, s_ref):
    rx0 = r_ref[0, :][:, None]   # (BR, 1)
    ry0 = r_ref[1, :][:, None]
    rx1 = r_ref[2, :][:, None]
    ry1 = r_ref[3, :][:, None]
    sx0 = s_ref[0, :][None, :]   # (1, BS)
    sy0 = s_ref[1, :][None, :]
    sx1 = s_ref[2, :][None, :]
    sy1 = s_ref[3, :][None, :]
    return (rx0 <= sx1) & (sx0 <= rx1) & (ry0 <= sy1) & (sy0 <= ry1)


def _count_kernel(r_ref, s_ref, out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    hits = _block_hits(r_ref, s_ref)
    out_ref[...] += jnp.sum(hits.astype(jnp.int32), axis=0, keepdims=True)


def _mask_kernel(r_ref, s_ref, out_ref):
    out_ref[...] = _block_hits(r_ref, s_ref)


def count_pallas(r4: jax.Array, s4: jax.Array, br: int = DEFAULT_BR,
                 bs: int = DEFAULT_BS, interpret: bool = False) -> jax.Array:
    """r4: (4, N), s4: (4, M), N % br == 0, M % bs == 0 -> (1, M) int32
    per-s hit counts (the join count is their sum)."""
    n, m = r4.shape[1], s4.shape[1]
    return pl.pallas_call(
        _count_kernel,
        grid=(m // bs, n // br),
        in_specs=[
            pl.BlockSpec((4, br), lambda j, i: (0, i)),
            pl.BlockSpec((4, bs), lambda j, i: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bs), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, m), jnp.int32),
        interpret=interpret,
    )(r4, s4)


def mask_pallas(r4: jax.Array, s4: jax.Array, br: int = DEFAULT_BR,
                bs: int = DEFAULT_BS, interpret: bool = False) -> jax.Array:
    """r4: (4, N), s4: (4, M) -> (N, M) bool intersection table."""
    n, m = r4.shape[1], s4.shape[1]
    grid = (n // br, m // bs)
    return pl.pallas_call(
        _mask_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((4, br), lambda i, j: (0, i)),
            pl.BlockSpec((4, bs), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((br, bs), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.bool_),
        interpret=interpret,
    )(r4, s4)
