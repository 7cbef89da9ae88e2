"""Scenario: multi-device spatial analytics over every visible device.

Shows the SPMD path end-to-end: MapReduce-style distributed partitioning
(sample → hilbert shuffle → per-device reduce), cost-model LPT packing,
tile-parallel join with both dedup strategies, straggler factors.

    PYTHONPATH=src python examples/distributed_join.py [--devices N]

``--devices N`` forces N virtual host devices
(``--xla_force_host_platform_device_count``), so the multi-device path
runs on a laptop exactly as it would on an N-chip mesh.
"""
import os
import sys

if "--devices" in sys.argv:
    _n = int(sys.argv[sys.argv.index("--devices") + 1])
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count={_n}")

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.core import metrics  # noqa: E402
from repro.core.compat import use_compile_cache  # noqa: E402
from repro.core.partition import partition_counts  # noqa: E402
from repro.data import spatial_gen  # noqa: E402
from repro.kernels.mbr_join import ref as oracle  # noqa: E402
from repro.query import engine, parallel_partition as pp  # noqa: E402

use_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
key = jax.random.PRNGKey(0)
r = spatial_gen.dataset("osm", key, 6000)
s = spatial_gen.dataset("pi", jax.random.PRNGKey(5), 4000)
mesh = Mesh(np.array(jax.devices()), ("d",))
n_dev = mesh.devices.size
print(f"{n_dev} device(s)")

# 1. distributed partitioning (paper §5.1)
parts, stats = pp.parallel_partition(key, r, 300, mesh, "d")
counts, copies = partition_counts(r, parts)
print(f"distributed partition: k={int(parts.k())} dropped={stats['dropped']} "
      f"coverage={float(metrics.coverage(copies)):.3f}")

# 2. planned, balanced join — LPT vs round-robin packing
want = int(oracle.intersect_count(r, s))
for packer in ["lpt", "round_robin"]:
    plan = engine.plan_join("bsp", r, s, 300, n_dev, packer=packer)
    got = engine.run_join_count(plan, mesh, "d", dedup="rp")
    assert got == want, (got, want)
    print(f"{packer:>12}: join={got} makespan-skew={plan.stats['skew']:.3f}")

# 3. paper-faithful MASJ dedup agrees with zero-comm reference-point dedup
plan = engine.plan_join("slc", r, s, 300, n_dev)
masj = engine.run_join_pairs_masj(plan, mesh, "d", max_pairs_per_tile=8192)
print(f"MASJ sort-unique dedup: {masj} == rp dedup: {want}")
assert masj == want
print("OK")
