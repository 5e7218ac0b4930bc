"""Compile-only rehearsals of the benchmark's cells for the TPU v5e: the
programs the windows drive, and the plain reference the checks run, at
the cells' own sizes, by the chip's compiler for a described (not
attached) ``v5e:2x2``: one device for the one-chip cells, the 2x2 mesh
for the four-chip cell.  The reserve-day rollout at its 288 lanes is
compiled by ``tests/test_tpu_compile.py``; the seconds-tier reference
(about a minute and a half a compile here) runs in every chip run.

Nothing runs, so these say nothing about results or times.  They catch a
program the chip's compiler refuses, one that does not fit a chip's
16 GB, and a sharded step that grew a collective.  The topology is
described inside a module fixture, never at import: only one process at
a time may load the TPU's library, and every test worker imports this
file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

HBM_BYTES = 16 * 10**9          # one v5e chip
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


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


def _cell(name):
    import bench_tiny
    from bench import run as bench_run

    find = bench_tiny.with_later(bench_run.find_cell)
    _, cell, config, traffic = find(name)
    return cell, config, traffic


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype,
                                       sharding=sharding), tree)


def _peak_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _chunk(traffic, n):
    """The first ``n`` scenarios of a cell's grid, stacked as the program
    stacks a chunk."""
    from bench import common
    from repro.grid.scenarios import build_scenario_batch

    grid = common.scenario_grid(traffic, 0)[:n]
    return build_scenario_batch(common.to_specs(grid),
                                h_max=traffic["horizon_h"])


def test_schedule_sweep_chunk_step(one_chip):
    import repro.core.engine as eng
    from bench import common

    _, config, traffic = _cell("schedule-sweep")
    cfg = common.engine_config(config, with_seconds=False)
    lanes = traffic["chunk"]
    compiled = eng._sweep_step_jit.lower(
        cfg, _abstract(eng.summary_init(cfg), one_chip),
        _abstract(_chunk(traffic, lanes), one_chip),
        jax.ShapeDtypeStruct((lanes,), jnp.float32,
                             sharding=one_chip)).compile()
    assert _peak_bytes(compiled) < HBM_BYTES


def test_service_tick(one_chip):
    from bench import common
    from repro.service.state import SiteStore, _service_step

    _, config, traffic = _cell("service-ffr-1024")
    store = SiteStore(common.engine_config(config), traffic["n_sites"],
                      horizon_h=traffic["horizon_h"])
    flags = jax.ShapeDtypeStruct((traffic["n_sites"],), jnp.bool_,
                                 sharding=one_chip)
    compiled = _service_step.lower(
        store.cfg, store.sched_s, _abstract(store.state, one_chip),
        flags, flags).compile()
    assert _peak_bytes(compiled) < HBM_BYTES


def test_four_chip_sweep_step_has_no_collectives(topo):
    import repro.core.engine as eng
    from bench import common

    _, config, traffic = _cell("reserve-sweep-4chip")
    mesh = Mesh(np.asarray(topo.devices), (eng._SCENARIO_AXIS,))
    lanes = NamedSharding(mesh, P(eng._SCENARIO_AXIS))
    cfg = common.engine_config(config, with_seconds=True)
    n = traffic["chunk"]
    agg = jax.tree.map(lambda x: np.zeros((len(topo.devices),)
                                          + np.shape(x), np.float32),
                       eng.summary_init(cfg))
    try:
        compiled = eng._sweep_step_sharded(cfg, mesh).lower(
            _abstract(agg, lanes), _abstract(_chunk(traffic, n), lanes),
            jax.ShapeDtypeStruct((n,), jnp.float32,
                                 sharding=lanes)).compile()
    finally:
        # the cache is keyed on device ids, which the described devices
        # share with real ones: never leave a described-chip program there
        eng.clear_sharded_cache()
    text = compiled.as_text()
    assert not [c for c in COLLECTIVES if c in text]
    assert _peak_bytes(compiled) < HBM_BYTES


def test_reference_hourly_block(one_chip):
    from bench import reference

    _, config, traffic = _cell("schedule-sweep")
    block = 8192
    tab = reference.scenario_table(
        [dict(country="SE", seed=0, start_day=15, mw=10.0, pue_design=1.2,
              horizon_h=traffic["horizon_h"], product="FFR", rho=0.2,
              event_seed=0, mix="train")], traffic["horizon_h"])
    tab = {k: np.repeat(v, block, 0) for k, v in tab.items()}
    e = config["engine"]
    compiled = reference._scenarios_jit.lower(
        _abstract(tab, one_chip), n_hosts=e["n_hosts"],
        chips=e["chips_per_host"], chip_tdp=e["chip_tdp"], e_max=e["e_max"],
        events_per_day=e["events_per_day"],
        max_freq_events=e["max_freq_events"], warmup_s=e["warmup_s"],
        with_seconds=False, dt=jnp.float32).compile()
    assert _peak_bytes(compiled) < HBM_BYTES


def test_reference_service_block(one_chip):
    from bench import reference

    _, config, traffic = _cell("service-ffr-1024")
    block, ticks = 256, 8192
    tab = reference.scenario_table(
        [dict(country="SE", seed=0, start_day=15, mw=10.0, pue_design=1.2,
              horizon_h=traffic["horizon_h"], product="FFR", rho=0.2,
              event_seed=0, mix="train")], traffic["horizon_h"])
    tab = {k: np.repeat(v, block, 0) for k, v in tab.items()}
    e = config["engine"]
    compiled = reference._service_jit.lower(
        _abstract(tab, one_chip),
        jax.ShapeDtypeStruct((ticks, block), jnp.bool_, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        n_hosts=e["n_hosts"], chips=e["chips_per_host"],
        chip_tdp=e["chip_tdp"], warmup_s=e["warmup_s"],
        sched_s=traffic["horizon_h"] * 3600, dt=jnp.float32).compile()
    assert _peak_bytes(compiled) < HBM_BYTES

