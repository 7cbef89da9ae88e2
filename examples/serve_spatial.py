"""Scenario: batched range + kNN serving over a partitioned layout.

Stages an OSM-like dataset once per layout, then streams query batches
through the SPMD serving step — routed/pruned (the default) vs the
dense oracle sweep, and replicated vs owner-routed *sharded* tiles —
printing queries/sec, the per-query partition fan-out that separates
the layouts (the paper's boundary-object cost, workload-facing), and
the per-device resident tile bytes that sharding divides by D.

    PYTHONPATH=src python examples/serve_spatial.py [--devices N]

``--devices N`` forces N virtual host devices
(``--xla_force_host_platform_device_count``), so the all_to_all
exchange path runs on a laptop exactly as it would on an N-chip mesh.
"""
import os
import sys

if __name__ == "__main__" and "--devices" in sys.argv:
    _n = int(sys.argv[sys.argv.index("--devices") + 1])
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count={_n}")

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core.compat import use_compile_cache
from repro.data import spatial_gen
from repro.serve import ServeConfig, SpatialServer

N, Q, K = 20_000, 1024, 10

if __name__ == "__main__":
    use_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    mbrs = spatial_gen.dataset("osm", jax.random.PRNGKey(0), N)
    mesh = Mesh(np.array(jax.devices()), ("d",))
    n_dev = len(mesh.devices.ravel())
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    c = jax.random.uniform(k1, (Q, 2))
    s = jax.random.uniform(k2, (Q, 2)) * 0.03
    qboxes = jnp.concatenate([c - s, c + s], axis=-1)
    pts = jax.random.uniform(k3, (Q, 2))

    print(f"serving {Q}-query batches over {N} objects, "
          f"{n_dev} device(s)")
    for method in ["fg", "bsp", "slc", "bos", "str", "hc"]:
        srv = SpatialServer.from_method(method, mbrs, 500, mesh=mesh)
        ssrv = SpatialServer.from_method(
            method, mbrs, 500, ServeConfig(placement="sharded"),
            mesh=mesh)
        for s_ in (srv, ssrv):                        # warm the jit cache
            s_.range_counts(qboxes)
        srv.range_counts(qboxes, pruned=False)
        t0 = time.perf_counter()
        counts, stats = srv.range_counts(qboxes)      # routed candidates
        dt = time.perf_counter() - t0
        t0 = time.perf_counter()
        srv.range_counts(qboxes, pruned=False)        # dense oracle
        dt_dense = time.perf_counter() - t0
        t0 = time.perf_counter()
        scounts, sstats = ssrv.range_counts(qboxes)   # owner-routed shards
        dt_sh = time.perf_counter() - t0
        assert np.array_equal(np.asarray(counts), np.asarray(scounts))
        nn_ids, _, _, kstats = ssrv.knn(pts, K)
        print(f"{method:>4}: pruned {Q / dt:>9.0f} q/s "
              f"(dense {Q / dt_dense:>9.0f}, sharded {Q / dt_sh:>9.0f}, "
              f"f_max {stats['f_max']:>3d})  "
              f"fanout {stats['fanout_mean']:.2f}  "
              f"chunk-skip {srv.chunk_skip_rate(qboxes):.2f}  "
              f"knn fanout {kstats['fanout_mean']:.2f}  "
              f"replication {srv.stats['replication']:.3f}  "
              f"resident/dev {srv.resident_tile_bytes() / 2**20:6.2f} MiB "
              f"repl vs {ssrv.resident_tile_bytes() / 2**20:6.2f} MiB "
              f"sharded")

    # streaming: stage 90% with slack, append the rest, keep serving
    head, tail = mbrs[: 9 * N // 10], mbrs[9 * N // 10:]
    srv = SpatialServer.from_method("bsp", head, 500,
                                    ServeConfig(slack=1024))
    t0 = time.perf_counter()
    for i in range(0, tail.shape[0], 256):
        rep = srv.append(tail[i:i + 256])
    dt = time.perf_counter() - t0
    counts, _ = srv.range_counts(qboxes)
    full = SpatialServer.from_method("bsp", mbrs, 500)
    fcounts, _ = full.range_counts(qboxes)
    assert np.array_equal(np.asarray(counts), np.asarray(fcounts))
    print(f"append: {tail.shape[0] / dt:>9.0f} obj/s streamed into slack "
          f"(restages {srv.stats['restages']}, answers == full restage)")
