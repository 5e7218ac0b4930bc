import os
import sys

# Tests run on the single real CPU device (the dry-run forces 512 devices
# in its own process only -- never here).
_root = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, os.path.join(_root, "src"))
# repo root, so the sweep-engine tests can import the benchmarks package
# (benchmarks/e8_multicountry.py hosts the vmapped E8 sweep under test)
sys.path.insert(0, _root)

# Test-size traffic of the benchmark cells that tests/bench/bench_tiny.py,
# a file of the accepted benchmark, does not list yet: the benchmark's CPU
# tests run every cell at test size and look its traffic up there.
sys.path.insert(0, os.path.join(_root, "tests", "bench"))
import bench_tiny  # noqa: E402

bench_tiny.TINY_TRAFFIC.setdefault(
    "ce-fcr-day", dict(countries=["DE", "CH"], weather_draws=1,
                       rhos=[0.1, 0.3], event_draws=1, horizon_h=5))

# Deterministic hypothesis profile for CI: derandomized (fixed example
# stream run-to-run), bounded example budget, no deadline (jit compiles
# on the first example dwarf any per-example budget).  Guarded: the
# container may only have the tests/_hypothesis_compat.py shim, whose
# no-op settings has no register_profile.
try:
    from hypothesis import HealthCheck, settings as _hyp_settings

    _hyp_settings.register_profile(
        "ci",
        derandomize=True,
        deadline=None,
        max_examples=24,
        suppress_health_check=list(HealthCheck),
    )
    _hyp_settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "ci"))
except ImportError:
    pass
