"""Fixtures of the benchmark's CPU tests.  The chip look is replaced
inside the tests by handing ``bench.run.run`` the CPU devices."""
import argparse

import pytest

from bench_tiny import tiny_find_cell


@pytest.fixture
def tiny(monkeypatch):
    """Run a cell at test size on the CPU: ``tiny(name, seed, seconds,
    trace)`` returns the result line's object."""
    import jax

    import repro.launch.compile_cache as compile_cache
    from bench import run as bench_run

    # the tests keep JAX's persistent cache off, as the rest of the suite
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(bench_run, "find_cell",
                        tiny_find_cell(bench_run.find_cell))

    def run(name, seed=3, seconds=1.0, trace=0):
        args = argparse.Namespace(workload=name, seed=seed, seconds=seconds,
                                  trace=trace)
        return bench_run.run(args, devices=jax.devices())
    return run
