"""Compile-only rehearsals for the TPU v5e: the programs ``chip_smoke.py``
runs, compiled here at the smoke's sizes by the chip's own compiler for a
described (not attached) ``v5e:2x2`` topology.

Nothing runs, so these say nothing about results or times.  They catch
what the CPU tests cannot: a kernel the Mosaic lowering refuses, a
program that does not fit one chip's 16 GB, and a sharded step that
grew a collective.  The topology is described inside a module-scoped
fixture (never at import): only one process at a time may load the
TPU's library, and pytest-xdist workers all import this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

import repro.core.engine as eng
from benchmarks.e9_reserve import build_e9_batch, engine_config
from benchmarks.engine_fleet import FLEET_CHUNK, fleet_cfg
from chip_smoke import collectives
from repro.grid.scenarios import product_specs, scenario_chunk
from repro.kernels.pid_update import pid_update
from repro.service.state import SiteStore, _service_step

HBM_BYTES = 16 * 10**9          # one v5e chip
SERVICE_SITES = 1024
PID_CHIPS = 100_000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _abstract(tree, sharding):
    """Arrays -> ShapeDtypeStructs placed on a described device."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype,
                                       sharding=sharding), tree)


def _peak_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_engine_seconds_fits_one_chip(one_chip):
    """The offline engine at the full E9 size: 288 scenario-days x 24 h."""
    _, batch = build_e9_batch()
    cfg = engine_config()
    n, T = batch.n, batch.h_max * 3600
    key = jax.ShapeDtypeStruct((n, 2), jnp.uint32, sharding=one_chip)
    compiled = eng._engine_seconds_jit.lower(
        cfg, "summary", _abstract(batch, one_chip),
        jax.ShapeDtypeStruct((n, T), jnp.float32, sharding=one_chip),
        None, key, key).compile()
    assert _peak_bytes(compiled) < HBM_BYTES


def test_service_step_compiles(one_chip):
    """The online service's donated batched tick at 1024 resident sites."""
    store = SiteStore(eng.EngineConfig(), SERVICE_SITES, horizon_h=24)
    flags = jax.ShapeDtypeStruct((SERVICE_SITES,), jnp.bool_,
                                 sharding=one_chip)
    compiled = _service_step.lower(
        store.cfg, store.sched_s, _abstract(store.state, one_chip),
        flags, flags).compile()
    assert _peak_bytes(compiled) < HBM_BYTES


def test_sweep_step_compiles(one_chip):
    """The streamed sweep's chunk step at the fleet bench's 512 lanes."""
    cfg = fleet_cfg()
    specs = product_specs(seeds=range(-(-FLEET_CHUNK // 6)), horizon_h=24)
    batch = scenario_chunk(specs, 0, FLEET_CHUNK, h_max=24)
    compiled = eng._sweep_step_jit.lower(
        cfg, _abstract(eng.summary_init(cfg), one_chip),
        _abstract(batch, one_chip),
        jax.ShapeDtypeStruct((FLEET_CHUNK,), jnp.float32,
                             sharding=one_chip)).compile()
    assert _peak_bytes(compiled) < HBM_BYTES


def test_pid_update_lowers_to_mosaic(one_chip):
    """The Pallas PID kernel at 10^5 chips compiles as a TPU kernel, not
    as interpreted XLA ops."""
    x = jax.ShapeDtypeStruct((PID_CHIPS,), jnp.float32, sharding=one_chip)
    compiled = pid_update.lower(x, x, x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_sweep_step_has_no_collectives(topo):
    """The scenario-sharded sweep step on the four-chip mesh keeps one
    aggregate lane per device: no cross-device traffic in the step."""
    mesh = Mesh(np.asarray(topo.devices), (eng._SCENARIO_AXIS,))
    lanes = NamedSharding(mesh, P(eng._SCENARIO_AXIS))
    n_dev = len(topo.devices)
    cfg = fleet_cfg()
    specs = product_specs(seeds=range(-(-FLEET_CHUNK // 6)), horizon_h=24)
    batch = scenario_chunk(specs, 0, FLEET_CHUNK, h_max=24)
    agg = jax.tree.map(lambda x: np.zeros((n_dev,) + np.shape(x),
                                          np.float32),
                       eng.summary_init(cfg))
    try:
        step = eng._sweep_step_sharded(cfg, mesh)
        text = step.lower(
            _abstract(agg, lanes), _abstract(batch, lanes),
            jax.ShapeDtypeStruct((FLEET_CHUNK,), jnp.float32,
                                 sharding=lanes)).compile().as_text()
    finally:
        # the cache is keyed on device ids, which the described devices
        # share with real ones: never leave a described-chip program there
        eng.clear_sharded_cache()
    found = collectives(text)
    assert not found, f"sharded sweep step contains {found}"
