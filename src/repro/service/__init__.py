"""Online multi-site control service (the always-on serving surface).

``state``    -- SiteStore: stacked per-site EngineState, one donated-buffer
                batched engine step, retrace-free admit/evict churn.
``server``   -- ServiceServer: asyncio dispatch loop, UDP/in-process feed
                ingestion, island-bypass FFR triggers, per-site quarantine.
``loadgen``  -- LoadGen: Poisson trigger storms for benchmarks and tests.

Exports resolve lazily (PEP 562) so ``python -m repro.service.server``
does not import the submodule twice.
"""
_EXPORTS = {
    "SiteStore": "state", "StoreState": "state",
    "ServiceConfig": "server", "ServiceServer": "server",
    "TICK_MAGIC": "server", "encode_tick": "server", "demo_batch": "server",
    "LoadGen": "loadgen", "LoadGenConfig": "loadgen",
}

__all__ = list(_EXPORTS)


def __getattr__(name):
    if name in _EXPORTS:
        import importlib

        mod = importlib.import_module(f"repro.service.{_EXPORTS[name]}")
        return getattr(mod, name)
    raise AttributeError(f"module 'repro.service' has no attribute {name!r}")
