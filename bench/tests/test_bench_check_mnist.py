"""`correct` of the training cell mnist.fleet_k64 at a test size: a sound
run passes, the control and each planted fault fail under its limits (see
checks.py), and so do the faults bench/calibrate.py reads on the chip."""
import pytest

from bench import calibrate
from bench.harness import compare, train_cell
from bench.tests import checks

NAME = "mnist.fleet_k64"


def _cell():
    return checks.file_cell("cnn-mnist", "fleet_k64", NAME, checks.TRAIN)


@pytest.fixture(scope="module")
def verdicts():
    return checks.sound_and_control(_cell())


def test_sound_run_is_correct(verdicts):
    assert verdicts[0]["correct"]


def test_control_is_not_correct(verdicts):
    assert not verdicts[1]["correct"]


@pytest.mark.parametrize("plant", [checks.unchanged, checks.half_batch,
                                   checks.altered])
def test_fault_is_not_correct(monkeypatch, plant):
    checks.with_fault(monkeypatch, plant)
    assert not checks.training_verdict(_cell())["correct"]


@pytest.fixture(scope="module")
def first_round():
    run = train_cell.TrainingRun(_cell(), 5)
    run.setup(warm=False)
    prog = run.program_outputs()
    run.release()
    return run, prog, run.reference()


@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_calibration_fault_is_not_correct(first_round, fault):
    run, prog, ref = first_round
    out = (calibrate.unchanged(run, prog) if fault == "unchanged"
           else calibrate.altered(prog))
    numbers = run.numbers(out, ref)
    assert not compare.verdict(numbers, run.cell.limits["limits"])["correct"]
    if fault == "unchanged":
        assert numbers["client_change_gap"] == pytest.approx(1.0)
