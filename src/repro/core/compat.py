"""JAX entry-point helpers shared across the repository.

``shard_map`` and ``all_to_all`` are the single spelling every call
site (and reprolint) uses for the SPMD primitives, so a future API move
lands in one place.  ``use_compile_cache`` is for entry points only
(scripts, examples, benchmarks) — the library never configures a cache
on import.
"""
from __future__ import annotations

import os

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` with keyword-only specs."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def all_to_all(x, axis_name: str):
    """Device transpose: ``x[(D, ...)] -> (D, ...)`` where output row
    ``j`` is what device ``j`` held in *its* row for this device.

    The one exchange shape the serving stack uses (leading axis =
    mesh-axis size, ``split_axis=concat_axis=0``).  ``tiled=True``
    keeps the leading axis in place (row ``j`` of the result came from
    device ``j``).
    """
    return jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                              tiled=True)


def use_compile_cache(root: str) -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads it
    itself; nothing else is configured).  Otherwise the cache lives at
    the fixed path ``<root>/.jax_cache`` — fixed, because the path is
    part of what makes a later run find the entries.  -> the directory.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
