"""The control of each cell at smoke size on the CPU: the plain reference
in the precision below the configuration's (fp8 for bf16) has to fail the
cell's limits, while the program passes them, on three seeds. On the chip
the same readings are taken at the cell's own size by `calibrate.py`."""
import pytest

from benchmarks.chip.tests import smoke

SEEDS = (2**31 + 101, 7, 4_000_000_003)


@pytest.fixture(autouse=True)
def _smoke(monkeypatch):
    smoke.use_smoke_program(monkeypatch)


@pytest.mark.parametrize("cell", ["qwen2.5-3b.decode_heavy"])
def test_control_fails_where_the_program_passes(cell):
    driver, st, spec = smoke.state(cell, seed=SEEDS[0])
    driver.setup(st)
    rows = driver.calibrate(st, SEEDS)
    limits = spec.limits
    for row in rows:
        assert all(row[k] <= lim for k, lim in limits.items()), row
        assert any(row[f"control_{k}"] > lim for k, lim in limits.items()), row
