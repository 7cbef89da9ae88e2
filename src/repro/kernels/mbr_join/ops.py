"""Public jit'd wrappers for the mbr_join kernel.

Handles padding to block multiples (with never-intersecting sentinel
boxes), component-major layout, and interpret mode off the TPU
(the CPU test path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.geometry import SENTINEL_BOX
from . import kernel

_SENTINEL = jnp.array(SENTINEL_BOX, jnp.float32)


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pad_cm(mbrs: jax.Array, block: int) -> jax.Array:
    """(N, 4) -> component-major (4, N_pad) with sentinel padding."""
    n = mbrs.shape[0]
    pad = (-n) % block
    if pad:
        mbrs = jnp.concatenate(
            [mbrs, jnp.broadcast_to(_SENTINEL, (pad, 4))], axis=0)
    return mbrs.T


@functools.partial(jax.jit, static_argnames=("br", "bs", "interpret"))
def join_count(r: jax.Array, s: jax.Array, br: int = kernel.DEFAULT_BR,
               bs: int = kernel.DEFAULT_BS,
               interpret: bool | None = None) -> jax.Array:
    """Total intersecting (r, s) pairs. r: (N, 4), s: (M, 4)."""
    if interpret is None:
        interpret = _interpret_default()
    r4 = _pad_cm(r.astype(jnp.float32), br)
    s4 = _pad_cm(s.astype(jnp.float32), bs)
    parts = kernel.count_pallas(r4, s4, br, bs, interpret=interpret)
    return jnp.sum(parts)


@functools.partial(jax.jit, static_argnames=("br", "bs", "interpret"))
def join_mask(r: jax.Array, s: jax.Array, br: int = kernel.DEFAULT_BR,
              bs: int = kernel.DEFAULT_BS,
              interpret: bool | None = None) -> jax.Array:
    """(N, M) boolean intersection table (un-padded view)."""
    if interpret is None:
        interpret = _interpret_default()
    n, m = r.shape[0], s.shape[0]
    r4 = _pad_cm(r.astype(jnp.float32), br)
    s4 = _pad_cm(s.astype(jnp.float32), bs)
    full = kernel.mask_pallas(r4, s4, br, bs, interpret=interpret)
    return full[:n, :m]
