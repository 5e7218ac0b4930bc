"""The control of each check at test size: the plain reference computed
in bfloat16 in the program's place fails at least one of the cell's
numbers, while the float32 reference passes its own comparison."""
import jax
import pytest

from bench import common

CELLS = ["reserve-day", "schedule-sweep", "service-ffr-1024"]


def _fails(cell, numbers) -> bool:
    lim = common.limits(cell)
    return any(numbers[k] > lim[k] for k in lim)


@pytest.mark.parametrize("cell", CELLS)
def test_the_bfloat16_control_is_not_correct(cell, monkeypatch):
    import bench_tiny
    import repro.launch.compile_cache as compile_cache
    from bench import control
    from bench import run as bench_run

    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    find = bench_tiny.tiny_find_cell(bench_run.find_cell)
    _, w, config, traffic = find(cell)
    driver = bench_run.load_module(
        bench_run.BENCH / "drivers" / f"{traffic['driver']}.py")
    ctx = bench_run.Context(w, config, traffic, 31, 1.0, False, jax.devices())
    got = control.control_numbers(driver, ctx, 31, 1.0)
    assert _fails(cell, got["control"]), got
    if got["program"] is not None:
        assert not _fails(cell, got["program"]), got
