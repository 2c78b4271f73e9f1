"""The comparison that decides ``correct``, at a size a CPU holds: a run
whose train step is broken underneath (state returned unchanged; half of
the batch left out) is not correct, and neither is the control in the
program's place. A sound run passes (``test_bench_sound.py``). The chip's
own readings, at the cells' sizes, set ``bench/limits/``; see PERF.md."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from bench_cells import CELLS, PEAKS, SEED, cmp, control, run, tiny  # noqa: E402


def broken(fault):
    from repro.train import step as train_step

    real = train_step.make_train_step

    def make(*args, **kwargs):
        step = real(*args, **kwargs)

        def unchanged(state, batch):
            return state, step(state, batch)[1]

        def half_batch(state, batch):
            return step(state, {k: v[: v.shape[0] // 2] for k, v in batch.items()})

        return {"unchanged": unchanged, "half_batch": half_batch}[fault]

    return make


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
@pytest.mark.parametrize("name", sorted(CELLS))
def test_broken_step_is_not_correct(name, fault, monkeypatch):
    from repro.train import step as train_step

    monkeypatch.setattr(train_step, "make_train_step", broken(fault))
    res = run.run_cell(tiny(name), SEED, 0.3, False, PEAKS)
    assert not res["correct"], res["check"]
    if fault == "unchanged":
        assert res["check"]["change_gap"]["value"] == pytest.approx(1.0)


# each cell's control: ADCs a bit coarser where the cell reads through them
STAND_INS = [(name, "adc_below" if tiny(name)["traffic"]["fidelity"] else "control") for name in sorted(CELLS)]


@pytest.mark.parametrize("name,stand_in", STAND_INS)
def test_control_is_not_correct(name, stand_in):
    """The reference one precision below the cell's: float8 matrix products
    for the lossless step, ADCs a bit coarser for the analog read."""
    cell = tiny(name)
    got = control.stand_in_readings(cell, SEED, modes=(stand_in,))
    correct, numbers, _ = cmp.compare(got[stand_in], got["reference"], cell["limits"])
    assert not correct, numbers
