"""The planning layer's program spans and counts as the RL cell reads
them: the new readers, their silence where the program has no such span
or count, and the program's spans on the harness's host clock."""
import time

import pytest

from bench.harness import device, rl_cell, spec
from bench.harness import trace as trace_mod
from bench.tests import checks

NAME = "mnist.rl_k64"
NEW = ("ppo_sync_ms.rl", "ppo_launch_ms.rl", "latency_model_ms.rl",
       "ppo_syncs.rl", "ppo_launches.rl")


@pytest.fixture(scope="module")
def readers():
    cell = spec.load_cell(NAME)
    assert set(NEW) <= set(cell.readers)
    return cell.readers


def test_new_metrics_are_planning_metrics_of_the_rl_cell():
    per_layer = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}
    for name in NEW:
        m = per_layer[name]
        assert m["layer"] == "planning" and m["moves"] == "rl_round_ms"
        assert m["workloads"] == [NAME]
        assert m["source"] == ("program_counter" if m["unit"] == "count"
                               else "program_span")


def test_readers_on_a_hand_built_context(readers):
    ctx = {"rounds": 4,
           "spans": {"ppo.sync": 0.008, "ppo.put": 0.002,
                     "ppo.dispatch": 0.006, "latency.assess": 0.001,
                     "latency.local_times": 0.002, "latency.rewards": 0.0004,
                     "server.plan_wave": 0.02},
           "counts": {"ppo.syncs": 34, "ppo.launches": 18}}
    got = {n: readers[n](ctx) for n in NEW}
    assert got["ppo_sync_ms.rl"] == pytest.approx(2.0)
    assert got["ppo_launch_ms.rl"] == pytest.approx(2.0)
    assert got["latency_model_ms.rl"] == pytest.approx(0.85)
    assert got["ppo_syncs.rl"] == pytest.approx(8.5)
    assert got["ppo_launches.rl"] == pytest.approx(4.5)


class OlderTracer:
    """A program tracer with events and no counts."""
    enabled, events = True, []


@pytest.fixture
def older_tracer():
    from repro.obs import trace as obs_trace
    prev = obs_trace.current()
    obs_trace.enable(OlderTracer())
    yield
    obs_trace.enable(prev)               # whichever tracer was installed


def test_readers_are_silent_where_the_program_has_no_such_span(
        readers, older_tracer):
    # the context and tracer of a program without planning spans or counts
    older = {"rounds": 4, "spans": {"server.plan_wave": 0.02,
                                    "server.feedback_wave": 0.01}}
    assert all(readers[n](older) is None for n in NEW)
    empty = dict(older, rounds=0, counts={"ppo.syncs": 3})
    assert all(readers[n](empty) is None for n in NEW)


@pytest.mark.parametrize("name,key", [("ppo_syncs.rl", "ppo.syncs"),
                                      ("ppo_launches.rl", "ppo.launches")])
def test_count_readers_fall_back_to_the_program_tracer(readers, name, key):
    from repro.obs import trace as obs_trace
    ctx = {"rounds": 4, "spans": {}}
    prev = obs_trace.current()
    try:
        obs_trace.disable()
        assert readers[name](ctx) is None          # tracing off
        obs_trace.enable(OlderTracer())
        assert readers[name](ctx) is None
        tr = obs_trace.enable(obs_trace.Tracer())
        tr.count(key, 6)
        assert readers[name](ctx) == pytest.approx(1.5)
        # a context that carries counts is read instead of the tracer
        assert readers[name](dict(ctx, counts={key: 2})) == \
            pytest.approx(0.5)
    finally:
        obs_trace.enable(prev)           # whichever tracer was installed


def test_program_spans_give_idle_gaps_their_innermost_label():
    host = [("bench.ppo1.act", 0.0, 10.0), ("ppo1.act", 1.0, 9.0),
            ("ppo.dispatch", 2.0, 3.0), ("ppo.sync", 4.0, 8.0)]
    label = trace_mod._labeller(host)
    assert label(0.5) == "bench.ppo1.act"
    assert label(1.5) == "ppo1.act"
    assert label(2.5) == "ppo.dispatch"
    assert label(5.0) == "ppo.sync"
    assert label(9.5) == "bench.ppo1.act"


@pytest.fixture(scope="module")
def traced_rl_run():
    """A shrunk RL cell traced by the program's tracer; its window's
    wrapper intervals recorded on the host clock."""
    from repro.obs import trace as obs_trace
    cell = checks.shrunk_cell(NAME, checks.RL)
    run = rl_cell.RLRun(cell, seed=11, traced=True)
    try:
        run.setup()
        run.probe.recording = True
        res = run.window(0.3, device.CompileMeter())
        run.probe.recording = False
        wrappers = list(run.probe.intervals)
        program = run.tracer.host_intervals()
        # the tracer stays on while the readers read its counts, as in a
        # traced run
        yield cell, res, wrappers, program
    finally:
        obs_trace.disable()


def test_program_act_spans_lie_inside_the_harness_wrappers(traced_rl_run):
    _, _, wrappers, program = traced_rl_run
    for agent in ("ppo1", "ppo2"):
        outer = [(a, b) for n, a, b in wrappers if n == f"bench.{agent}.act"]
        inner = [(a, b) for n, a, b in program if n == f"{agent}.act"]
        assert len(outer) == len(inner) > 0
        for (a, b), (c, d) in zip(sorted(outer), sorted(inner)):
            assert a <= c < d <= b


def test_traced_window_gives_every_new_metric(traced_rl_run, readers):
    cell, res, _, _ = traced_rl_run
    ctx = res["layer"]
    assert ctx["rounds"] > 0
    got = {n: readers[n](ctx) for n in NEW}
    assert all(v is not None and v > 0 for v in got.values()), got
    # every round splits its key and acts with both agents; every act
    # reads twice
    assert got["ppo_launches.rl"] >= 4
    assert got["ppo_syncs.rl"] >= 4
    # the existing readers read what they read before
    for n in ("ppo_act_ms.rl", "ppo_update_ms.rl"):
        assert cell.readers[n](ctx) > 0


def test_account_splits_rounds_by_self_time():
    from bench import account
    ev = lambda name, parent, dur: {"ph": "X", "clock": "wall",
                                    "name": name, "parent": parent,
                                    "ts": 0.0, "dur": dur}
    events = [ev("ppo.sync", "ppo1.act", 0.002), ev("ppo.put", "ppo1.act",
                                                    0.001),
              ev("ppo1.act", "server.plan_wave", 0.004),
              ev("latency.assess", "server.plan_wave", 0.001),
              ev("server.plan_wave", None, 0.006),
              ev("server.feedback_wave", None, 0.002),
              {"ph": "X", "clock": "virtual", "name": "wave", "ts": 0.0,
               "dur": 5.0}]
    rows = account.self_times(events)
    assert rows[("ppo1.act", "server.plan_wave")][1] == pytest.approx(0.001)
    assert rows[("server.plan_wave", None)][1] == pytest.approx(0.001)
    assert ("wave", None) not in rows
    got = account.account([0.005, 0.005], events, {"ppo.syncs": 9})
    assert got["round_ms"] == pytest.approx(5.0)
    assert got["uncovered_ms"] == pytest.approx(1.0)
    assert got["uncovered_share"] == pytest.approx(0.2)
    assert got["counts"] == {"ppo.syncs": 4.5}
    # the self times and the remainder add up to the rounds' wall time
    assert sum(r[3] for r in got["spans"]) + got["uncovered_ms"] == \
        pytest.approx(got["round_ms"])
