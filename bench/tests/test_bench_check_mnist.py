"""`correct` of the training cell mnist.fleet_k64 at a test size: a sound
run passes, the control and each planted fault fail under its limits (see
checks.py), and so do the faults bench/calibrate.py reads on the chip.
The per-client walk gives the numbers of the whole-round check it
replaced (`checks.whole_round`), for the program, the control and every
fault, and fails the same numbers."""
import jax.numpy as jnp
import pytest

from bench import calibrate
from bench.harness import compare, train_cell
from bench.tests import checks

NAME = "mnist.fleet_k64"
PLANTS = [checks.unchanged, checks.half_batch, checks.altered]


def _cell():
    return checks.file_cell("cnn-mnist", "fleet_k64", NAME, checks.TRAIN)


def _both(run, sides, whole):
    """{side: (the walk's numbers, the whole round's)}."""
    n = len(run.first.plan[0])
    ref = checks.whole_round(run.first)
    old = {s.name: checks.whole_numbers(
               run.first, whole.get(s.name) or checks.whole_outputs(s, n),
               ref)
           for s in sides}
    new = run.readings(sides)
    return {s.name: (new[s.name]["numbers"], old[s.name]) for s in sides}


@pytest.fixture(scope="module")
def first_round():
    run = train_cell.TrainingRun(_cell(), 5)
    run.setup(warm=False)
    run.release()
    sides = [run.program(), run.reference("control", jnp.bfloat16),
             run.reference("half_batch", keep="half"),
             calibrate.unchanged(run), calibrate.altered(run)]
    # the references' rounds as the whole-round check computed them
    whole = {"control": checks.whole_round(run.first, jnp.bfloat16),
             "half_batch": checks.whole_round(
                 run.first, keep=run.config["batch_size"] // 2)}
    yield run, _both(run, sides, whole)
    run.close()


@pytest.fixture(scope="module")
def faults():
    """{plant: (the walk's numbers, the whole round's)} of a run with the
    fault planted beneath the harness's wrappers."""
    out = {}
    for plant in PLANTS:
        with pytest.MonkeyPatch.context() as mp:
            checks.with_fault(mp, plant)
            run = train_cell.TrainingRun(_cell(), 5)
            run.setup(warm=False)
        run.release()
        try:
            out[plant.__name__] = _both(run, [run.program()], {})["program"]
        finally:
            run.close()
    return out


def _verdict(numbers):
    return compare.verdict(numbers, _cell().limits["limits"])


def test_sound_run_is_correct(first_round):
    assert _verdict(first_round[1]["program"][0])["correct"]


def test_control_is_not_correct(first_round):
    assert not _verdict(first_round[1]["control"][0])["correct"]


@pytest.mark.parametrize("plant", PLANTS)
def test_fault_is_not_correct(faults, plant):
    assert not _verdict(faults[plant.__name__][0])["correct"]


@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_calibration_fault_is_not_correct(first_round, fault):
    numbers = first_round[1][
        "state_unchanged" if fault == "unchanged" else "altered"][0]
    assert not _verdict(numbers)["correct"]
    if fault == "unchanged":
        assert numbers["client_change_gap"] == pytest.approx(1.0)


def _failed(numbers):
    return {k for k, v in _verdict(numbers)["compared"].items()
            if not v["value"] <= v["limit"]}


@pytest.mark.parametrize("case", ["program", "control", "half_batch",
                                  "state_unchanged", "altered"])
def test_walk_gives_the_whole_round_numbers(first_round, case):
    new, old = first_round[1][case]
    assert new == pytest.approx(old, rel=1e-6, abs=0)
    assert _failed(new) == _failed(old)


@pytest.mark.parametrize("plant", PLANTS)
def test_walk_gives_the_whole_round_numbers_of_a_fault(faults, plant):
    new, old = faults[plant.__name__]
    assert new == pytest.approx(old, rel=1e-6, abs=0)
    assert _failed(new) == _failed(old) != set()


def test_check_removes_the_spilled_clients():
    run = train_cell.TrainingRun(_cell(), 6)
    run.setup(warm=False)
    run.release()
    spilled = run.cap.store.dir
    assert len(list(spilled.iterdir())) == run.traffic["k_per_round"]
    assert _verdict(run.check())["correct"]
    assert not spilled.parent.exists()
