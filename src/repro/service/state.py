"""SiteStore: persistent per-site ``EngineState`` for the online service.

The offline sweeps replay whole horizons in one ``jit(vmap(scan))``; the
service instead holds a *resident* population of sites -- every site's
:class:`~repro.core.engine.EngineState` pytree stacked along a leading
site axis -- and advances all of them together with ONE jitted,
**donated-buffer** batched :func:`~repro.core.engine.engine_step` per
tick:

  * ``donate_argnums`` on the stacked :class:`StoreState` means the tick
    writes back into the same device buffers every call (verified by
    pointer identity in ``tests/test_service.py``): steady-state ticking
    allocates nothing per tick on the host side, which is what lets the
    benchmark pin RSS over thousands of ticks,
  * sites are admitted/evicted **by index** into a fixed-capacity store:
    the slot index is a *traced* scalar, so churn at any slot reuses the
    single compiled admit/evict/step programs -- no retrace, ever
    (``step_cache_size`` stays 1, pinned in tests),
  * lanes are independent: an inactive (or quarantined) lane's state is
    frozen bit-exactly via a per-lane ``where``, so admitting or evicting
    neighbours never perturbs a surviving site's trajectory -- the churn
    bit-identity guarantee the tests pin.

Per-tick demand is synthesised in-graph from the same
``twin.HostLoadParams`` constants the offline engine uses, but with the
white noise drawn per second (``fold_in(fast_key, t)``): the service
cannot amortise an hour block because each site is at a different point
in its life, and in production this input is *measured* site telemetry
anyway -- the synthesis is the stand-in feed.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import repro.core.engine as engine_lib
import repro.core.tier3 as tier3_lib
import repro.core.twin as twin_lib
import repro.grid.markets as markets
import repro.workload.model as workload_lib
from repro.core.engine import EngineConfig, EngineParams, EngineState
from repro.grid.scenarios import ScenarioBatch
from repro.obs import trace


class StoreState(NamedTuple):
    """Everything the batched tick touches, stacked along a site axis."""

    engine: EngineState      # every leaf (S, ...)
    params: EngineParams     # per-site hourly tables, (S, ...)
    load: twin_lib.HostLoadParams  # per-site demand-synthesis constants
    mw: jax.Array            # (S,) site IT design power
    active: jax.Array        # (S,) bool: slot holds a live site
    t: jax.Array             # (S,) int32 seconds since admission


@partial(jax.jit, static_argnames=("cfg", "sched_s"), donate_argnums=(2,))
def _service_step(cfg: EngineConfig, sched_s: int, st: StoreState,
                  below, enabled) -> tuple[StoreState, jax.Array]:
    """ONE donated-buffer batched tick over every site lane.

    ``below`` is the per-site frequency-below-trigger flag the server
    assembled from its feeds (including island-bypass pending triggers);
    ``enabled`` masks quarantined lanes out of the advance.  The schedule
    tables wrap at ``sched_s`` so an always-on site cycles its horizon.

    Returns the new state and the tick's flags, one ``(2, S)`` bool
    array -- row 0 ``trig`` (a reserve event triggered this tick), row 1
    ``shed`` (the shed is being served this tick) -- so the server reads
    both back in one fetch.
    """
    run = st.active & enabled

    def one(params, lp, es, t, blw, go):
        t_sched = jnp.mod(t, sched_s)
        # live demand row: per-second white noise on the shared slow-wave
        # model (the offline block counter cannot be amortised here)
        fast = jax.random.normal(
            jax.random.fold_in(lp.fast_key, t), (1,) + lp.mean.shape)
        row = twin_lib.host_loads_rows(
            lp, jnp.asarray(t_sched, jnp.float32)[None], fast)[0]
        new, (sec, _) = engine_lib.engine_step(
            cfg, params, es, (row, blw, go, t_sched))
        # freeze non-running lanes bit-exactly (churn independence)
        new = jax.tree.map(lambda a, b: jnp.where(go, a, b), new, es)
        return new, (sec.trig & go, sec.shed & go)

    eng, (trig, shed) = jax.vmap(one)(st.params, st.load, st.engine, st.t,
                                      below, run)
    return (st._replace(engine=eng, t=st.t + run.astype(jnp.int32)),
            jnp.stack([trig, shed]))


@partial(jax.jit, donate_argnums=(0,))
def _admit_at(st: StoreState, idx, engine0: EngineState,
              params: EngineParams, lp, mw) -> StoreState:
    """Write one site into slot ``idx`` (traced: any slot, one program)."""
    def write(a, b):
        return a.at[idx].set(b)

    return StoreState(
        engine=jax.tree.map(write, st.engine, engine0),
        params=jax.tree.map(write, st.params, params),
        load=jax.tree.map(write, st.load, lp),
        mw=st.mw.at[idx].set(mw),
        active=st.active.at[idx].set(True),
        t=st.t.at[idx].set(0))


@partial(jax.jit, donate_argnums=(0,))
def _evict_at(st: StoreState, idx) -> StoreState:
    """Free slot ``idx``.  The lane's state stays in place (frozen by the
    active mask), so eviction is one scatter into the mask -- survivors'
    buffers are untouched."""
    return st._replace(active=st.active.at[idx].set(False))


@partial(jax.jit, static_argnames=("cfg",))
def _site_params_jit(cfg: EngineConfig, ci, t_amb, mask, mw, pue_design,
                     product_idx, rho, mix_idx) -> EngineParams:
    """Admission slow path: Tier-3 tables for a batch of new sites.

    The same selection + armed-band physics the offline rollout hoists
    before its scan (``engine._rollout_one``), vmapped over the admitted
    batch; compiled once per (cfg, horizon) and reused for every
    admission wave.
    """
    def one(ci, t_amb, mask, mw, pd, pi, r, mi):
        out = engine_lib._hourly_one(cfg, ci, t_amb, mask, mw, pd, pi, r,
                                     mi)
        vh = tier3_lib.event_verdict(out["mu_h"], t_amb, out["rho_h"], pi,
                                     pd, pue_aware=cfg.pue_aware)
        min_dur = jnp.asarray(markets.MIN_DURATION_S)[pi]
        return EngineParams(
            mu_h=out["mu_h"], rho_h=out["rho_h"], t_amb_h=t_amb,
            rho_it_h=vh["rho_it"], min_dur_i=min_dur.astype(jnp.int32),
            pue_design=pd, clock_w=jnp.asarray(workload_lib.CLOCK_W)[mi])

    return jax.vmap(one)(ci, t_amb, mask, mw, pue_design, product_idx,
                         rho, mix_idx)


def _zeros_params(capacity: int, h_max: int) -> EngineParams:
    # distinct buffers per leaf: donation rejects aliased arguments
    def z_h():
        return jnp.zeros((capacity, h_max), jnp.float32)

    return EngineParams(mu_h=z_h(), rho_h=z_h(), t_amb_h=z_h(),
                        rho_it_h=z_h(),
                        min_dur_i=jnp.zeros((capacity,), jnp.int32),
                        pue_design=jnp.ones((capacity,), jnp.float32),
                        clock_w=jnp.zeros((capacity,), jnp.float32))


class SiteStore:
    """Fixed-capacity resident store of per-site engine state.

    The hot path is :meth:`step`; admission/eviction are the slow path
    (still compiled-once, traced-index programs).  ``capacity`` and the
    schedule horizon are static -- churn changes data, never shapes.
    """

    def __init__(self, cfg: EngineConfig, capacity: int, horizon_h: int,
                 *, seed: int = 0):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.cfg = cfg
        self.capacity = capacity
        self.horizon_h = int(horizon_h)
        self.sched_s = self.horizon_h * 3600
        keys = jax.random.split(jax.random.PRNGKey(seed), 2 * capacity)
        engine0 = jax.jit(jax.vmap(partial(engine_lib.engine_init, cfg)))(
            keys[:capacity])
        load0 = jax.jit(jax.vmap(partial(twin_lib.host_load_params,
                                         cfg.n_hosts)))(keys[capacity:])
        self.state = StoreState(
            engine=engine0, params=_zeros_params(capacity, self.horizon_h),
            load=load0, mw=jnp.zeros((capacity,), jnp.float32),
            active=jnp.zeros((capacity,), bool),
            t=jnp.zeros((capacity,), jnp.int32))
        self._free = list(range(capacity - 1, -1, -1))
        self._init_keys = keys  # fresh per-admission state seeds

    # -- occupancy ----------------------------------------------------------
    @property
    def n_active(self) -> int:
        return self.capacity - len(self._free)

    @property
    def free_slots(self) -> int:
        return len(self._free)

    # -- slow path: churn by index ------------------------------------------
    def admit_batch(self, batch: ScenarioBatch) -> list[int]:
        """Admit every scenario in ``batch`` into free slots; returns the
        slot indices (the site handles the server routes by)."""
        if batch.h_max != self.horizon_h:
            raise ValueError(
                f"admitted batch horizon {batch.h_max} h != store horizon "
                f"{self.horizon_h} h (fixed at construction)")
        if batch.n > len(self._free):
            raise ValueError(
                f"admit of {batch.n} sites exceeds {len(self._free)} free "
                f"slots (capacity {self.capacity})")
        params = _site_params_jit(
            self.cfg, batch.ci, batch.t_amb, batch.mask, batch.mw,
            batch.pue_design, batch.product_idx, batch.reserve_rho,
            batch.mix_idx)
        load_keys, scan_keys = engine_lib.scenario_keys(batch)
        load = jax.jit(jax.vmap(partial(twin_lib.host_load_params,
                                        self.cfg.n_hosts)))(load_keys)
        eng = jax.jit(jax.vmap(partial(engine_lib.engine_init,
                                       self.cfg)))(scan_keys)
        slots = []
        for i in range(batch.n):
            slot = self._free.pop()
            lane = jax.tree.map(lambda a, i=i: a[i], (eng, params, load))
            self.state = _admit_at(self.state, jnp.asarray(slot, jnp.int32),
                                   *lane, batch.mw[i])
            slots.append(slot)
        return slots

    def evict(self, slot: int) -> None:
        if slot in self._free:
            raise ValueError(f"slot {slot} is already free")
        self.state = _evict_at(self.state, jnp.asarray(slot, jnp.int32))
        self._free.append(slot)

    # -- hot path ------------------------------------------------------------
    def step(self, below=None, enabled=None) -> jax.Array:
        """One donated-buffer batched tick over every lane.

        ``below``/``enabled`` are ``(S,)`` bool arrays, numpy or device,
        and default to all-clear/all-enabled.  Returns the tick's
        ``(2, S)`` bool flags (rows ``trig``, ``shed``), still on the
        device: the caller fetches them in one transfer."""
        if below is None:
            below = np.zeros((self.capacity,), bool)
        if enabled is None:
            enabled = np.ones((self.capacity,), bool)
        # the enqueue; the flags go in as they are, so their host->device
        # copies ride inside the launch.  JAX returns before the device
        # finishes, so the device's share shows in the read-back
        with trace.span("service.dispatch"):
            self.state, flags = _service_step(
                self.cfg, self.sched_s, self.state, below, enabled)
        return flags

    # -- introspection (tests/bench) ----------------------------------------
    def snapshot(self) -> EngineState:
        """Host copy of the stacked engine state (safe across donation)."""
        return jax.tree.map(np.asarray, self.state.engine)

    def site_tables(self, slots: Sequence[int]) -> dict:
        """Host view of admitted sites' hour-0 operating points (the rows
        the server arms its island register file from)."""
        idx = np.asarray(list(slots), np.int64)
        return dict(
            mu0=np.asarray(self.state.params.mu_h)[idx, 0],
            rho0=np.asarray(self.state.params.rho_h)[idx, 0],
            min_dur_s=np.asarray(self.state.params.min_dur_i)[idx],
            mw=np.asarray(self.state.mw)[idx],
        )

    @staticmethod
    def step_cache_size() -> int:
        """Compiled-program count of the hot tick (1 == churn never
        retraced; the no-retrace regression gate)."""
        return _service_step._cache_size()

    @staticmethod
    def clear_step_cache() -> None:
        _service_step._clear_cache()
