"""Compile-only checks: the serving and join kernels at the chip smoke's
shapes, compiled for a described TPU v5e (no chip attached).

Interpret mode accepts block shapes, casts and fast-memory footprints
that the TPU compiler refuses; these tests run the real compiler on the
``ops`` wrappers with ``interpret=False`` so such a refusal fails here,
not on the chip.  Nothing executes.  The topology is described inside a
module fixture (never at import), so every test worker collects the same
tests and only the worker given this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.mbr_join import ops as mops
from repro.kernels.range_probe import ops

# one staged 1M-object osm/bsp layout at payload 4000: 256 tiles of
# 11,520 slots (90 chunks), routed at f_max 16, frontend top rung 512
Q, T, CAP, F = 512, 256, 11_520, 16
C = CAP // ops.CHUNK


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """Shape factory placing abstract arguments on one described chip;
    the persistent compilation cache is off meanwhile (entries compiled
    for a described chip cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache
    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt,
                                                         sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


PROBES = {
    "gathered_counts_skip": lambda q, t, cb, k, a: ops.gathered_counts_skip(
        q, t, cb, k, interpret=False, alive=a),
    "gathered_mask_skip": lambda q, t, cb, k, a: ops.gathered_mask_skip(
        q, t, cb, k, interpret=False, alive=a),
    "gathered_counts": lambda q, t, cb, k, a: ops.gathered_counts(
        q, t, k, interpret=False, alive=a),
    "probe_counts": lambda q, t, cb, k, a: ops.probe_counts(
        q, t, interpret=False, alive=a),
}


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("with_alive", [False, True],
                         ids=["all_live", "alive"])
@pytest.mark.parametrize("name", sorted(PROBES))
def test_range_probe_compiles_for_v5e(shape, name, with_alive):
    args = (shape((Q, 4)), shape((T, CAP, 4)), shape((T, C, 4)),
            shape((Q, F), jnp.int32),
            shape((T, CAP), jnp.bool_) if with_alive else None)
    assert "tpu_custom_call" in _compiled_text(PROBES[name], *args)


@pytest.mark.parametrize("fn", [mops.join_count, mops.join_mask],
                         ids=["join_count", "join_mask"])
def test_mbr_join_compiles_for_v5e(shape, fn):
    boxes = shape((8192, 4))
    text = _compiled_text(lambda r, s: fn(r, s, interpret=False),
                          boxes, boxes)
    assert "tpu_custom_call" in text
