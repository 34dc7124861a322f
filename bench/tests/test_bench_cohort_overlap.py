"""The cohort engine's counts as the fleet cell reads them: cohort_overlap
from a context's counts or the program tracer's, and its silence where the
program counts no cohort groups (as a program without the two-deep
pipeline)."""
import pytest

from bench.harness import spec

NAME = "mnist.fleet_k64"


class OlderTracer:
    """A program tracer with events and no counts."""
    enabled, events = True, []


@pytest.fixture(scope="module")
def read():
    return spec.load_cell(NAME).readers["cohort_overlap"]


def test_cohort_overlap_is_a_cohort_engine_metric_of_the_fleet_cell():
    m = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}[
        "cohort_overlap"]
    assert (m["layer"], m["moves"], m["unit"], m["source"]) == (
        "cohort engine", "round_s", "%", "program_counter")
    assert m["workloads"] == [NAME]


def test_cohort_overlap_on_a_hand_built_context(read):
    ctx = {"rounds": 1, "spans": {}}
    assert read(dict(ctx, counts={"cohort.groups": 14,
                                  "cohort.overlapped": 13})) == \
        pytest.approx(100.0 * 13 / 14)
    assert read(dict(ctx, counts={"cohort.groups": 1})) == 0.0
    assert read(dict(ctx, counts={"ppo.syncs": 3})) is None


def test_cohort_overlap_falls_back_to_the_program_tracer(read):
    from repro.obs import trace as obs_trace
    ctx = {"rounds": 1, "spans": {}}
    prev = obs_trace.current()
    try:
        obs_trace.disable()
        assert read(ctx) is None                   # tracing off
        obs_trace.enable(OlderTracer())
        assert read(ctx) is None
        tr = obs_trace.enable(obs_trace.Tracer())
        assert read(ctx) is None                   # no cohort counted
        tr.count("cohort.groups", 12)
        tr.count("cohort.overlapped", 11)
        assert read(ctx) == pytest.approx(100.0 * 11 / 12)
    finally:
        obs_trace.enable(prev)           # whichever tracer was installed
