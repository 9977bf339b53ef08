"""The device path compiles for a TPU v5e at real size, without a chip.

JAX can describe a TPU topology that is not attached and hand its devices
to the compiler, which then refuses exactly what the chip's compiler would:
misaligned kernel blocks, too much fast memory, a program that does not
fit. Each test here compiles one program of the stream engine's device path
at the size ``chip_smoke.py`` runs: a 2^22-id key domain, a window of 4,
15 tasks, ~2M tuples per interval, routing tables of 128 and 2048 entries,
and ``key_stats`` at 262144 tuples x 65536 keys. The sharded steps compile
on a 4-device mesh of the described ``v5e:2x2`` host, and must carry their
``all-to-all``.

Nothing runs, so nothing here says a result is right or how long it takes.
The topology is described inside a fixture (never at import): only one
process may load the TPU library, and every test worker imports this file.
"""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from jax.sharding import (AxisType, Mesh, NamedSharding,  # noqa: E402
                          PartitionSpec as P, SingleDeviceSharding)

DOMAIN = 1 << 22            # key ids; the ring has DOMAIN + 1 rows
WINDOW = 4
TASKS = 15
TUPLES_CAP = 1 << 21        # ~2M tuples per interval, padded to pow2
STATS_TUPLES, STATS_KEYS = 262_144, 65_536


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.array(topo.devices[:4]), ("shard",),
                axis_types=(AxisType.Auto,))


def _arg(shape, sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _donating(jitted, **kwargs):
    """The state steps as the TPU runs them: the ring buffers are donated
    (``streams/device.py`` switches donation off on CPU only)."""
    return jax.jit(jitted.__wrapped__, donate_argnums=(0, 1), **kwargs)


@pytest.mark.parametrize("table", [128, 2048])
def test_routing_lookup_compiles_over_dense_domain(one_chip, table):
    from repro.kernels.routing_lookup import _routing_lookup

    compiled = _routing_lookup.lower(
        _arg((DOMAIN + 1,), one_chip), _arg((table,), one_chip),
        _arg((table,), one_chip), TASKS, seed=0,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_key_stats_compiles(one_chip):
    from repro.kernels.key_stats import _key_stats

    compiled = _key_stats.lower(
        _arg((STATS_TUPLES,), one_chip),
        _arg((STATS_TUPLES,), one_chip, jnp.float32), STATS_KEYS,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_interval_step_add_compiles_with_donation(one_chip):
    from repro.streams import device

    w1, d1 = WINDOW + 1, DOMAIN + 1
    compiled = _donating(device._interval_step_add).lower(
        _arg((w1, d1), one_chip), _arg((w1, d1), one_chip),
        _arg((d1,), one_chip), _arg((w1,), one_chip),
        _arg((w1,), one_chip)).compile()
    assert "input_output_alias" in compiled.as_text()
    # the ring (2 planes) plus the histogram and the 4 per-key outputs
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 2 * w1 * d1 * 4


def test_interval_step_max_compiles_with_donation(one_chip):
    from repro.streams import device

    w1, d1 = WINDOW + 1, DOMAIN + 1
    compiled = _donating(device._interval_step_max,
                         static_argnames=("n_tasks",)).lower(
        _arg((w1, d1), one_chip), _arg((w1, d1), one_chip),
        _arg((TUPLES_CAP,), one_chip), _arg((TUPLES_CAP,), one_chip),
        _arg((d1,), one_chip), _arg((w1,), one_chip),
        _arg((w1,), one_chip), n_tasks=TASKS).compile()
    assert "input_output_alias" in compiled.as_text()


def test_route_dense_compiles(one_chip):
    from repro.streams import device

    device._route_dense.lower(
        _arg((DOMAIN + 1,), one_chip), _arg((2048,), one_chip),
        _arg((2048,), one_chip), n_dest=TASKS, seed=0).compile()


def test_set_cols_compiles_with_donation(one_chip):
    from repro.streams import device

    w1, d1, n = WINDOW + 1, DOMAIN + 1, 1 << 18
    compiled = _donating(device._set_cols).lower(
        _arg((w1, d1), one_chip), _arg((w1, d1), one_chip),
        _arg((n,), one_chip), _arg((w1, n), one_chip),
        _arg((w1, n), one_chip)).compile()
    assert "input_output_alias" in compiled.as_text()


@pytest.mark.parametrize("mode", ["add", "max"])
def test_sharded_step_compiles_with_all_to_all(mesh, mode):
    from repro.streams import sharded

    s = mesh.shape["shard"]
    block = DOMAIN // s
    w1, g = WINDOW + 1, s * (block + 1)
    cap = TUPLES_CAP // s
    ring = NamedSharding(mesh, P(None, "shard"))
    chunks = NamedSharding(mesh, P("shard", None))
    rep = NamedSharding(mesh, P())
    build = sharded._build_step_add if mode == "add" \
        else sharded._build_step_max
    args = [_arg((w1, g), ring), _arg((w1, g), ring),
            _arg((s, cap), chunks)]
    if mode == "max":
        args.append(_arg((s, cap), chunks))
    args += [_arg((w1,), rep), _arg((w1,), rep)]
    compiled = build(mesh, s, block).lower(*args).compile()
    assert "all-to-all" in compiled.as_text()


def test_sharded_route_compiles(mesh):
    from repro.streams import sharded

    s = mesh.shape["shard"]
    rep = NamedSharding(mesh, P())
    fn = functools.partial(sharded._build_route, mesh, s, DOMAIN // s)
    compiled = fn(TASKS, 0).lower(_arg((2048,), rep),
                                  _arg((2048,), rep)).compile()
    assert compiled.as_text()
