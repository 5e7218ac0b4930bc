"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and
every phase it runs there passes here at a tiny size on the CPU (the
same calls and checks; only the sizes are cut)."""
import jax
import pytest

import chip_smoke
import repro.core.engine  # noqa: F401  (import order: engine before grid)
from repro.grid.scenarios import product_specs
from repro.launch import compile_cache


@pytest.fixture(autouse=True)
def cache_stays_off(monkeypatch):
    """The entry points the phases call place the persistent compile
    cache; the tests never turn it on."""
    for mod in (compile_cache, chip_smoke):
        monkeypatch.setattr(mod, "enable_compile_cache", lambda: "")
    before = jax.config.jax_compilation_cache_dir
    yield
    assert jax.config.jax_compilation_cache_dir == before


def test_refuses_without_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert "needs a TPU" in str(e.value.code)
    assert "'cpu'" in str(e.value.code)
    assert '"ok"' not in capsys.readouterr().out


PHASES = {
    "engine": lambda: chip_smoke.phase_engine(fast=True, n_cpu=4),
    "sweep": lambda: chip_smoke.phase_sweep(
        product_specs(seeds=range(2), horizon_h=24), chunk=8),
    "service": lambda: chip_smoke.phase_service(n_sites=8, n_ticks=12,
                                                horizon_h=1),
    "train": lambda: chip_smoke.phase_train(full=False, batch=2, seq=32,
                                            steps=2),
    "sharded": lambda: chip_smoke.phase_sharded(fast=True, chunk=4),
}


@pytest.mark.parametrize("phase", sorted(PHASES))
def test_phase_passes_on_cpu(phase, capsys):
    PHASES[phase]()
    assert f"[{phase}]" in capsys.readouterr().out
