"""The benchmark's own data and request generators.

Objects are Spider boxes (Vu, Migliorini, Eldawy, Belussi, "Spatial
Data Generators", SpatialGems 2019, section 3; Katiyar et al.,
"SpiderWeb", SIGSPATIAL 2020): a centre drawn from the configuration's
``distribution``, and a width and a height each uniform in
``[0, max_size]``, the box centred on the point.  Each distribution
is a file of its own, ``distributions/<name>.py``, with a
``centres(key, n)`` that returns ``(n, 2)`` points in the unit square.
The request generators are copies of ``chip_smoke.make_requests``,
kept here so that no change to the program can move the yardstick.
"""
from __future__ import annotations

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

DISTRIBUTIONS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "distributions")


@functools.lru_cache(maxsize=None)
def _spider(distribution: str):
    """The jitted generator of one distribution's boxes."""
    path = os.path.join(DISTRIBUTIONS, distribution + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_distribution_" + distribution, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def boxes(key, n: int, max_size: tuple):
        k_centre, k_size = jax.random.split(key)
        c = mod.centres(k_centre, n)
        half = 0.5 * jax.random.uniform(k_size, (n, 2)) * jnp.asarray(
            max_size, jnp.float32)
        return jnp.concatenate([c - half, c + half],
                               axis=-1).astype(jnp.float32)

    return boxes


def dataset(cfg: dict, seed: int):
    """The configuration's objects for ``seed``, made on the default
    device in one jitted call -> (n, 4) f32 device array."""
    return _spider(cfg["distribution"])(
        seed_key(seed), cfg["objects"], tuple(cfg["max_size"]))


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds may exceed 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def home_boxes(centres: np.ndarray, part_boxes: np.ndarray) -> np.ndarray:
    """For each point the first valid partition box containing it."""
    out = np.empty((len(centres), 4), np.float32)
    for lo in range(0, len(centres), 4096):
        c = centres[lo:lo + 4096]
        inside = ((part_boxes[None, :, 0] <= c[:, None, 0])
                  & (c[:, None, 0] <= part_boxes[None, :, 2])
                  & (part_boxes[None, :, 1] <= c[:, None, 1])
                  & (c[:, None, 1] <= part_boxes[None, :, 3]))
        out[lo:lo + 4096] = part_boxes[np.argmax(inside, axis=1)]
    return out


def range_boxes(rng, centres: np.ndarray, part_boxes: np.ndarray, n: int,
                half_side: tuple) -> np.ndarray:
    """Range boxes at the local zoom level: centred on random object
    centres, half-side a uniform fraction of the side of the partition
    that holds the centre (``chip_smoke.make_requests``)."""
    c = centres[rng.choice(len(centres), n, replace=n > len(centres))]
    home = home_boxes(c, part_boxes)
    side = np.minimum(home[:, 2] - home[:, 0], home[:, 3] - home[:, 1])
    half = side * rng.uniform(half_side[0], half_side[1], n)
    return np.concatenate([c - half[:, None], c + half[:, None]],
                          axis=1).astype(np.float32)


def knn_points(rng, centres: np.ndarray, n: int) -> np.ndarray:
    """kNN query points at random object centres."""
    return centres[rng.choice(len(centres), n,
                              replace=n > len(centres))].astype(np.float32)


def tenants(rng, shares: dict, n: int) -> list:
    """A tenant name per request, drawn by the mix's shares."""
    names = list(shares)
    p = np.asarray([shares[t] for t in names], np.float64)
    return [names[i] for i in rng.choice(len(names), n, p=p / p.sum())]


def arrivals(rng, rate: float, seconds: float) -> np.ndarray:
    """Open-loop Poisson arrivals with a fixed count: ``rate·seconds``
    sorted uniform times in ``[0, seconds)`` (a Poisson process given
    its count), so every seed offers the same amount of work."""
    n = int(round(rate * seconds))
    return np.sort(rng.uniform(0.0, seconds, n))
