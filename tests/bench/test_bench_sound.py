"""A sound run of each cell, at a size a CPU holds, is correct: the program
and the plain reference agree within the limits of that size."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from bench_cells import CELLS, PEAKS, SEED, run, tiny  # noqa: E402


@pytest.mark.parametrize("name", sorted(CELLS))
def test_sound_run_is_correct(name):
    res = run.run_cell(tiny(name), SEED, 0.3, False, PEAKS)
    assert res["correct"], res["check"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "check"
