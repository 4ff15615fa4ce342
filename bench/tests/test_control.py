"""The control (the reference computed in bfloat16, put in the program's
place) fails every cell's limits, at a size a test run holds.  On the
chip the same ``bench/control.py`` runs at each cell's own size."""

import pytest

from bench import control, harness

from conftest import CELLS, ROOT, tiny_overrides


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limits(cell):
    c = harness.load_cell(ROOT, cell)
    c.config.update(tiny_overrides(c.config))
    for seed in (1, 2, 3):
        gaps = control.control_gaps(c, seed, jobs=1)
        assert control.fails(c, gaps), gaps
