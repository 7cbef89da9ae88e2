"""Quickstart: the paper's pipeline in 40 lines.

Generates a skewed (OSM-like) dataset, partitions it with all six
algorithms, prints the paper's quality metrics, and runs a distributed
spatial join whose result is checked against the brute-force oracle.

    PYTHONPATH=src python examples/quickstart.py
"""
import os

import jax
import numpy as np
from jax.sharding import Mesh

from repro.core import metrics
from repro.core.compat import use_compile_cache
from repro.core.partition import api, partition_counts
from repro.data import spatial_gen
from repro.kernels.mbr_join import ref as oracle
from repro.query import engine

N, PAYLOAD = 4000, 250

use_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
key = jax.random.PRNGKey(0)
r = spatial_gen.dataset("osm", key, N)
s = spatial_gen.dataset("osm", jax.random.PRNGKey(1), N // 2)

print(f"{'method':>6} {'k':>5} {'λ':>8} {'stddev':>8} {'skew':>6}")
for method in ["fg", "bsp", "slc", "bos", "str", "hc"]:
    parts = api.partition(method, r, PAYLOAD)
    counts, copies = partition_counts(r, parts)
    print(f"{method:>6} {int(parts.k()):>5} "
          f"{float(metrics.boundary_ratio(counts, parts.valid, N)):>8.4f} "
          f"{float(metrics.balance_stddev(counts, parts.valid)):>8.2f} "
          f"{float(metrics.skew_ratio(counts, parts.valid)):>6.2f}")

mesh = Mesh(np.array(jax.devices()), ("d",) )
want = int(oracle.intersect_count(r, s))
plan = engine.plan_join("bos", r, s, PAYLOAD, jax.device_count())
got = engine.spatial_join_count(plan, mesh, "d")
print(f"\nspatial join |R ⋈ S| = {got} (oracle {want}) "
      f"tile-skew={plan.stats['skew']:.2f} λ_R={plan.stats['lambda_r']:.3f}")
assert got == want
print("OK")
