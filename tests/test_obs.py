"""Observability subsystem (DESIGN.md §16): tracer determinism, zero-cost
disable, Chrome trace schema, metrics registry, ServiceMetrics parity
across the registry refactor, deterministic dump, RL diagnostics."""
import dataclasses
import json
import time
from pathlib import Path

import numpy as np
import pytest

from repro.fl import FLEnvironment, FLSimConfig, HAPFLServer
from repro.obs import trace as obs_trace
from repro.obs.registry import (Counter, CounterVec, Gauge, Histogram,
                                IntHistogram, MetricsRegistry, Reservoir,
                                latency_stats)
from repro.obs.trace import (NULL_TRACER, VIRTUAL, WALL, Tracer,
                             validate_chrome_trace, wave_timing_summary)
from repro.service.metrics import ServiceMetrics
from repro.sim import BufferedPolicy, EventScheduler, SyncPolicy

CFG = FLSimConfig(dataset="mnist", n_train=300, n_test=80, n_clients=8,
                  k_per_round=4, batches_per_epoch=1, default_epochs=2,
                  batch_size=16)


@pytest.fixture(autouse=True)
def _tracer_reset():
    """Every test starts and ends with tracing disabled."""
    obs_trace.disable()
    yield
    obs_trace.disable()


def fresh_server(seed=3, **kw):
    return HAPFLServer(FLEnvironment(CFG), seed=seed, **kw)


# --------------------------------------------------------------------- #
# tracer core
# --------------------------------------------------------------------- #
def test_null_tracer_is_default_and_noop():
    tr = obs_trace.current()
    assert tr is NULL_TRACER and not tr.enabled
    with tr.span("x", foo=1) as s1, tr.annotation("y") as s2:
        assert s1 is s2          # one shared null context manager
    assert tr.span_at("x", 0, 1) is None
    assert tr.counter("c", {"v": 1}) is None


def test_enable_disable_singleton():
    t1 = obs_trace.enable()
    assert obs_trace.current() is t1 and t1.enabled
    assert obs_trace.enable() is t1          # idempotent
    t2 = Tracer()
    assert obs_trace.enable(t2) is t2        # explicit replacement
    obs_trace.disable()
    assert obs_trace.current() is NULL_TRACER


def test_span_nesting_and_chrome_schema():
    tr = Tracer()
    with tr.span("outer", a=1):
        with tr.span("inner"):
            pass
        tr.instant("tick")
    tr.set_virtual(5.0)
    tr.counter("load", {"x": 1, "none": None, "nan": float("nan")},
               clock=VIRTUAL)
    tr.span_at("wave", 2.0, 7.0, clock=VIRTUAL, tid="waves")
    stats = validate_chrome_trace(tr.to_chrome())
    assert stats["n_spans"] == 3 and stats["n_instants"] == 1
    assert stats["n_counters"] == 1
    assert stats["pids"] == [1, 2]           # wall + virtual tracks
    # inner span closed first but sorts inside outer (begin ts ordering)
    rows = [e for e in tr.to_chrome()["traceEvents"] if e.get("ph") == "X"
            and e["pid"] == 1]
    assert [r["name"] for r in rows] == ["outer", "inner"]
    assert rows[0]["dur"] >= rows[1]["dur"]
    # counter dropped the None/NaN series but kept the numeric one
    c = next(e for e in tr.events if e["ph"] == "C")
    assert c["args"] == {"x": 1.0}


def test_export_round_trips_and_validates(tmp_path):
    tr = Tracer()
    with tr.span("a"):
        pass
    p = tr.export(tmp_path / "t.json")
    stats = validate_chrome_trace(json.loads(Path(p).read_text()))
    assert stats["n_spans"] == 1


def test_validate_rejects_broken_traces():
    tr = Tracer()
    tr.span_at("a", 0.0, 1.0)
    good = tr.to_chrome()
    bad = json.loads(json.dumps(good))
    del bad["traceEvents"][-1]["ts"]
    with pytest.raises(ValueError, match="missing key"):
        validate_chrome_trace(bad)
    bad2 = json.loads(json.dumps(good))
    bad2["traceEvents"].append(dict(bad2["traceEvents"][-1], ts=-50.0))
    with pytest.raises(ValueError, match="monotonicity"):
        validate_chrome_trace(bad2)
    with pytest.raises(ValueError):
        validate_chrome_trace({"not": "a trace"})


def test_validate_rejects_bad_counter_values():
    tr = Tracer()
    good = tr.to_chrome()
    def counter(args):
        return dict(good, traceEvents=[{"name": "c", "ph": "C", "ts": 0.0,
                                        "pid": 1, "tid": 0, "args": args}])

    with pytest.raises(ValueError, match="non-finite"):
        validate_chrome_trace(counter({"x": float("nan")}))
    with pytest.raises(ValueError, match="non-finite"):
        validate_chrome_trace(counter({"x": 1.0, "y": float("inf")}))
    with pytest.raises(ValueError, match="no args series"):
        validate_chrome_trace(counter({}))


def test_validate_rejects_nonmonotonic_counter_track():
    """Counters with one name form one Perfetto track per pid regardless
    of tid — a ts regression across tids must be rejected even though
    each (pid, tid) stream alone is monotone."""
    base = {"name": "load", "ph": "C", "pid": 1, "args": {"x": 1.0}}
    trace = {"traceEvents": [dict(base, tid=0, ts=10.0),
                             dict(base, tid=1, ts=5.0)],
             "displayTimeUnit": "ms"}
    with pytest.raises(ValueError, match="counter track"):
        validate_chrome_trace(trace)
    # distinct names on the same pid are independent tracks: fine
    ok = {"traceEvents": [dict(base, tid=0, ts=10.0),
                          dict(base, name="other", tid=1, ts=5.0)],
          "displayTimeUnit": "ms"}
    assert validate_chrome_trace(ok)["n_counters"] == 2


def test_wave_timing_summary():
    spans = [{"args": {"assess": 1.0, "local": 2.0, "comm": 0.5,
                       "barrier": 0.25}},
             {"args": {"assess": 3.0, "local": 4.0, "comm": 1.5,
                       "barrier": 0.75}},
             None,                       # skipped agent / null span
             {"args": {"wave": 1}}]      # no phase breakdown -> filtered
    out = wave_timing_summary(spans)
    assert out["n_waves"] == 2
    assert out["assess"] == {"mean": 2.0, "max": 3.0, "total": 4.0}
    assert out["barrier"]["total"] == 1.0
    assert wave_timing_summary([]) is None


def _wall_spans(tracer, name=None):
    return [e for e in tracer.events if e["ph"] == "X"
            and e["clock"] == WALL and (name is None or e["name"] == name)]


def test_wall_spans_record_their_parent():
    tr = Tracer()
    with tr.span("outer"):
        with tr.span("mid"):
            with tr.span("leaf"):
                pass
        with tr.span("sibling"):
            pass
    with tr.span("alone"):
        pass
    parent = {e["name"]: e["parent"] for e in _wall_spans(tr)}
    assert parent == {"outer": None, "mid": "outer", "leaf": "mid",
                      "sibling": "outer", "alone": None}
    # virtual spans stay out of the wall tree
    with tr.span("outer2"):
        with tr.span("v", clock=VIRTUAL):
            with tr.span("inner2"):
                pass
    v = next(e for e in tr.events if e["name"] == "v")
    assert "parent" not in v
    assert _wall_spans(tr, "inner2")[0]["parent"] == "outer2"


def test_self_time_from_parents_sums_to_the_top_spans():
    tr = Tracer()
    with tr.span("top"):
        with tr.span("a"):
            time.sleep(0.002)
        with tr.span("b"):
            with tr.span("c"):
                time.sleep(0.001)
    spans = _wall_spans(tr)
    total = {e["name"]: e["dur"] for e in spans}
    child = {}
    for e in spans:
        if e["parent"] is not None:
            child[e["parent"]] = child.get(e["parent"], 0.0) + e["dur"]
    self_t = {n: total[n] - child.get(n, 0.0) for n in total}
    assert all(v >= 0.0 for v in self_t.values())
    assert sum(self_t.values()) == pytest.approx(total["top"], abs=1e-12)


def test_nested_spans_inherit_the_round_index():
    tr = Tracer()
    with tr.span("server.plan_wave", round=7):
        with tr.span("ppo1.act"):
            with tr.span("ppo.sync"):
                pass
        with tr.span("other", round=9):     # an explicit round wins
            with tr.span("deep"):
                pass
    with tr.span("outside"):
        pass
    rnd = {e["name"]: e.get("args", {}).get("round")
           for e in _wall_spans(tr)}
    assert rnd == {"server.plan_wave": 7, "ppo1.act": 7, "ppo.sync": 7,
                   "other": 9, "deep": 9, "outside": None}


def test_count_keeps_monotonic_totals():
    tr = Tracer()
    assert tr.counts == {}
    tr.count("ppo.syncs")
    tr.count("ppo.syncs", 11)
    tr.count("ppo.launches", 2)
    assert tr.counts == {"ppo.syncs": 12, "ppo.launches": 2}
    # counts are totals, not trace events
    assert not tr.events
    tr.clear()
    assert tr.counts == {}
    assert NULL_TRACER.count("ppo.syncs", 3) is None
    assert not hasattr(NULL_TRACER, "counts")


def test_host_intervals_are_on_the_perf_counter_clock():
    tr = Tracer()
    assert tr.wall0 <= time.perf_counter()
    before = time.perf_counter()
    with tr.span("a"):
        with tr.span("b"):
            time.sleep(0.001)
    after = time.perf_counter()
    tr.span_at("v", 0.0, 1.0, clock=VIRTUAL)          # not a wall span
    got = tr.host_intervals()
    assert [n for n, _, _ in got] == ["b", "a"]       # in closing order
    (_, b0, b1), (_, a0, a1) = got
    assert before <= a0 <= b0 < b1 <= a1 <= after
    assert b1 - b0 >= 0.001
    # clear() moves the origin; new spans stay on the same absolute clock
    tr.clear()
    t = time.perf_counter()
    with tr.span("c"):
        pass
    (_, c0, _), = tr.host_intervals()
    assert c0 >= t


# --------------------------------------------------------------------- #
# tracer determinism + zero-cost disable against the simulator
# --------------------------------------------------------------------- #
def _traced_sim_run(seed=3, waves=3):
    tracer = Tracer()
    obs_trace.enable(tracer)
    try:
        srv = fresh_server(seed=seed)
        sched = EventScheduler(srv, BufferedPolicy(buffer_m=2),
                               eval_accuracy=False)
        res = sched.run(waves=waves)
    finally:
        obs_trace.disable()
    return srv, res, tracer


def test_virtual_records_deterministic_across_runs():
    _, res_a, tr_a = _traced_sim_run()
    _, res_b, tr_b = _traced_sim_run()
    va, vb = tr_a.virtual_records(), tr_b.virtual_records()
    assert va and va == vb
    assert res_a.timing == res_b.timing and res_a.timing is not None


def test_tracing_does_not_perturb_the_simulation():
    """A traced run must be byte-identical to an untraced one on every
    simulation output. Tracing alone collects no RL diagnostics
    (`collect_rl_diag` is the one switch), so the records are equal
    whole, rl_diag (None on both sides) included."""
    srv_a = fresh_server()
    res_a = EventScheduler(srv_a, SyncPolicy()).run(waves=3)
    srv_b, res_b, _ = _traced_sim_run_sync(collect_rl_diag=False)
    assert len(srv_a.history) == len(srv_b.history) == 3
    for a, b in zip(srv_a.history, srv_b.history):
        assert a.rl_diag is None and b.rl_diag is None
        assert dataclasses.asdict(a) == dataclasses.asdict(b)
    assert res_a.sim_time == res_b.sim_time
    assert res_a.timing is None and res_b.timing is not None


def _traced_sim_run_sync(seed=3, waves=3, collect_rl_diag=False):
    tracer = Tracer()
    obs_trace.enable(tracer)
    try:
        srv = fresh_server(seed=seed)
        srv.collect_rl_diag = collect_rl_diag
        res = EventScheduler(srv, SyncPolicy()).run(waves=waves)
    finally:
        obs_trace.disable()
    return srv, res, tracer


def test_sim_trace_has_expected_structure():
    _, res, tr = _traced_sim_run()
    trace = tr.to_chrome()
    stats = validate_chrome_trace(trace)
    names = {e["name"] for e in trace["traceEvents"]}
    for want in ("sim.dispatch", "server.plan_wave", "server.train_wave",
                 "server.feedback_wave", "wave_barrier", "arrival",
                 "dispatch", "sim.load"):
        assert want in names, f"missing {want}"
    assert stats["pids"] == [1, 2]
    # timing summary totals are consistent with the recorded wave spans
    assert res.timing["n_waves"] >= 3
    for phase in ("assess", "local", "comm", "barrier"):
        assert res.timing[phase]["max"] >= 0.0


# --------------------------------------------------------------------- #
# metrics registry
# --------------------------------------------------------------------- #
def test_registry_instruments_roundtrip():
    r = MetricsRegistry()
    r.counter("c").inc(2.5)
    r.counter_vec("cv").inc("a", 3)
    r.gauge("g").set(7.0)
    r.int_histogram("ih").observe(4)
    h = r.histogram("h", edges=(1.0, 10.0))
    h.observe(0.5), h.observe(5.0), h.observe(50.0)
    r.reservoir("res").observe(0.25)
    state = r.pack()
    assert "res" not in state                 # reservoirs excluded by default
    assert state == {"c": 2.5, "cv": {"a": 3}, "g": 7.0, "ih": {"4": 1},
                     "h": {"edges": [1.0, 10.0], "buckets": [1, 1, 1],
                           "sum": 55.5, "count": 3}}
    r2 = MetricsRegistry()
    r2.counter("c"), r2.counter_vec("cv"), r2.gauge("g")
    r2.int_histogram("ih"), r2.histogram("h", edges=(1.0, 10.0))
    r2.unpack(state)
    assert r2.pack() == state
    assert json.dumps(r2.pack(), sort_keys=True) == \
        json.dumps(state, sort_keys=True)


def test_registry_get_or_create_and_kind_mismatch():
    r = MetricsRegistry()
    c = r.counter("x")
    assert r.counter("x") is c
    with pytest.raises(ValueError, match="already registered"):
        r.gauge("x")
    with pytest.raises(KeyError, match="unknown instrument"):
        r.unpack({"nope": 1})
    assert "x" in r and r["x"] is c and r.names() == ["x"]


def test_histogram_edge_mismatch_and_reservoir_bound():
    h = Histogram("h", edges=(1.0, 2.0))
    with pytest.raises(ValueError, match="edge mismatch"):
        h.unpack({"edges": [1.0, 3.0], "buckets": [0, 0, 0], "sum": 0.0,
                  "count": 0})
    with pytest.raises(ValueError, match="sorted"):
        Histogram("bad", edges=(2.0, 1.0))
    res = Reservoir("r", maxlen=4)
    for i in range(10):
        res.observe(float(i))
    assert list(res.samples) == [6.0, 7.0, 8.0, 9.0]
    assert res.stats()["n"] == 4
    assert latency_stats([]) is None


# --------------------------------------------------------------------- #
# ServiceMetrics: parity across the registry refactor + dump determinism
# --------------------------------------------------------------------- #
def _exercised_metrics():
    m = ServiceMetrics()
    m.bump("dispatch", 3)
    m.bump("submit", 2)
    m.bump("checkpoint")          # LOCAL_COUNT_KEYS: not checkpointed
    m.note_staleness(0)
    m.note_staleness(2)
    m.up_bytes += 123.456
    m.down_bytes += 7.0
    m.dispatch_s.append(0.001)
    m.submit_s.append(0.002)
    m.log(1.5, "dispatch", client=4)
    return m


def test_service_metrics_pack_schema_unchanged():
    """pack() must emit the exact pre-registry structure — service
    checkpoints round-trip bit-identically across the refactor."""
    m = _exercised_metrics()
    state = m.pack()
    assert sorted(state) == ["counts", "down_bytes", "staleness", "up_bytes"]
    assert state["counts"] == {"dispatch": 3, "submit": 2}   # no 'checkpoint'
    assert state["staleness"] == {"0": 1, "2": 1}
    assert isinstance(state["up_bytes"], float)
    m2 = ServiceMetrics()
    m2.unpack(json.loads(json.dumps(state)))      # via-JSON round trip
    assert json.dumps(m2.pack(), sort_keys=True) == \
        json.dumps(state, sort_keys=True)


def test_service_metrics_snapshot_keys_match_committed_artifact():
    """The snapshot surface bench_serve reads must keep serving the keys
    recorded in the committed serve_load artifact."""
    art = Path(__file__).resolve().parents[1] / "artifacts" / "bench" / \
        "serve_load.json"
    row = next(iter(json.loads(art.read_text()).values()))
    snap = _exercised_metrics().snapshot()
    for key in ("updates_per_sec", "aggregations_per_sec", "staleness_hist",
                "dispatch", "submit", "checkpoint", "up_bytes",
                "down_bytes"):
        assert key in snap and key in row
    assert snap["dispatch"]["n"] == 1


def test_dump_is_byte_deterministic(tmp_path, monkeypatch):
    monkeypatch.setattr(time, "perf_counter", lambda: 42.0)
    m = _exercised_metrics()
    m.snapshot()["counts"]["dispatch"]            # reads don't mutate
    m.dump(tmp_path / "a.json")
    m.dump(tmp_path / "b.json")
    a = (tmp_path / "a.json").read_bytes()
    assert a == (tmp_path / "b.json").read_bytes()
    # fresh but identically-exercised state dumps the same bytes
    m2 = _exercised_metrics()
    m2.dump(tmp_path / "c.json")
    assert a == (tmp_path / "c.json").read_bytes()
    # keys are sorted and floats rounded (no default=str stringification)
    data = json.loads(a)
    assert list(data) == ["events", "snapshot"]
    assert data["snapshot"]["up_bytes"] == 123.5   # round(…, 1) at source


def test_dump_rejects_non_json_types(tmp_path):
    m = ServiceMetrics()
    m.events.append({"t": 0.0, "event": "bad", "arr": np.arange(3)})
    with pytest.raises(TypeError, match="non-JSON-serializable"):
        m.dump(tmp_path / "x.json")
    # 0-dim numpy scalars are fine (converted via .item())
    m.events.clear()
    m.log(0.0, "ok", v=float(np.float64(1.25)))
    m.events.append({"t": 0.0, "event": "ok2", "v": np.float32(0.5)})
    m.dump(tmp_path / "y.json")
    assert json.loads((tmp_path / "y.json").read_text())


# --------------------------------------------------------------------- #
# RL diagnostics
# --------------------------------------------------------------------- #
def test_ppo_update_metrics_carry_diagnostics():
    srv = fresh_server()
    # buffer_size waves fill the buffer and trigger one PPO update
    B = srv.allocator.agent.cfg.buffer_size
    srv.pretrain_rl(B + 1)
    for agent in (srv.allocator.agent, srv.intensity.agent):
        assert agent.n_updates >= 1
        last = agent.last_update
        for k in ("loss", "approx_kl", "clip_fraction", "entropy",
                  "value_loss", "adv_mean", "adv_std"):
            assert k in last and np.isfinite(last[k])


def test_rl_diag_lands_on_round_records_when_traced():
    """Under tracing with `collect_rl_diag` set, the diagnostics land on
    the records and as trace counters (tracing alone collects none)."""
    tracer = Tracer()
    obs_trace.enable(tracer)
    try:
        srv = fresh_server()
        srv.collect_rl_diag = True
        B = srv.allocator.agent.cfg.buffer_size
        srv.pretrain_rl(B + 1)
    finally:
        obs_trace.disable()
    first, last = srv.history[0], srv.history[-1]
    assert set(first.rl_diag) == {"ppo1", "ppo2"}
    # pre-update waves: entropy/reward flow, update metrics still None
    assert first.rl_diag["ppo1"]["approx_kl"] is None
    assert isinstance(first.rl_diag["ppo1"]["entropy"], float)
    # post-update waves carry the optimizer-side diagnostics
    d = last.rl_diag["ppo2"]
    assert d["n_updates"] >= 1.0
    for k in ("approx_kl", "clip_fraction", "adv_mean", "adv_std",
              "value_loss"):
        assert isinstance(d[k], float), k
    # and the same numbers were emitted as trace counters
    names = {e["name"] for e in tracer.events if e["ph"] == "C"}
    assert {"rl.ppo1", "rl.ppo2", "rl.reward"} <= names


def test_untraced_rounds_have_no_rl_diag():
    srv = fresh_server()
    srv.run(2)
    assert all(r.rl_diag is None for r in srv.history)


# --------------------------------------------------------------------- #
# planning-layer spans and counts
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def traced_latency_rounds():
    """`buffer_size + 1` latency-only rounds, traced, against the same
    rounds untraced: (traced server, its tracer, untraced server)."""
    obs_trace.disable()
    plain = fresh_server()
    B = plain.allocator.agent.cfg.buffer_size
    for _ in range(B + 1):
        plain.run_round(latency_only=True)
    tracer = Tracer()
    obs_trace.enable(tracer)
    try:
        srv = fresh_server()
        for _ in range(B + 1):
            srv.run_round(latency_only=True)
    finally:
        obs_trace.disable()
    return srv, tracer, plain


@pytest.mark.parametrize("agent", ["ppo1", "ppo2"])
def test_acts_hold_put_dispatch_and_sync_children(traced_latency_rounds,
                                                  agent):
    srv, tracer, _ = traced_latency_rounds
    acts = _wall_spans(tracer, f"{agent}.act")
    assert len(acts) == len(srv.history)
    assert all(a["parent"] == "server.plan_wave" for a in acts)
    for child in ("ppo.put", "ppo.dispatch", "ppo.sync"):
        mine = [e for e in _wall_spans(tracer, child)
                if e["parent"] == f"{agent}.act"]
        assert len(mine) == len(acts), child
    # each act's children lie inside it and carry its round
    for a in acts:
        kids = [e for e in tracer.events if e.get("parent") == a["name"]
                and e["args"]["round"] == a["args"]["round"]]
        assert {k["name"] for k in kids} == {"ppo.put", "ppo.dispatch",
                                             "ppo.sync"}
        for k in kids:
            assert a["ts"] <= k["ts"]
            assert k["ts"] + k["dur"] <= a["ts"] + a["dur"] + 1e-9


def test_one_update_span_per_agent_when_the_buffer_fills(
        traced_latency_rounds):
    srv, tracer, _ = traced_latency_rounds
    for owner, agent in ((srv.allocator, "ppo1"), (srv.intensity, "ppo2")):
        assert owner.agent.name == agent
        assert owner.agent.n_updates == 1
        (upd,) = _wall_spans(tracer, f"{agent}.update")
        assert upd["parent"] == "server.feedback_wave"
        kids = {e["name"] for e in tracer.events
                if e.get("parent") == f"{agent}.update"}
        assert kids == {"ppo.put", "ppo.dispatch", "ppo.sync"}


def test_plan_and_feedback_spans_cover_the_latency_model(
        traced_latency_rounds):
    srv, tracer, _ = traced_latency_rounds
    n = len(srv.history)
    parent = {"latency.assess": "server.plan_wave",
              "latency.local_times": "server.plan_wave",
              "server.split_key": "server.plan_wave",
              "latency.rewards": None}
    for name, want in parent.items():
        spans = _wall_spans(tracer, name)
        assert len(spans) == (2 * n if name == "latency.rewards" else n)
        if want is not None:
            assert {e["parent"] for e in spans} == {want}
    # the rewards run inside each agent's feedback, under the wave's span
    assert {e["parent"] for e in _wall_spans(tracer, "latency.rewards")} \
        == {"server.feedback_wave"}


def test_sync_and_launch_counts_match_the_reads_the_code_makes(
        traced_latency_rounds):
    srv, tracer, _ = traced_latency_rounds
    agents = (srv.allocator.agent, srv.intensity.agent)
    acts = sum(len(_wall_spans(tracer, f"{a.name}.act")) for a in agents)
    updates = sum(a.n_updates for a in agents)
    # each act copies its action and its log-probability to the host,
    # each update its metrics vector; each call waits once
    reads = 2 * acts + updates
    assert tracer.counts["ppo.syncs"] == reads
    assert len(_wall_spans(tracer, "ppo.sync")) == acts + updates
    # a key split and its unstacking per round, one program per act and
    # per update
    rounds = len(srv.history)
    assert tracer.counts["ppo.launches"] == 2 * rounds + acts + updates
    assert set(tracer.counts) == {"ppo.syncs", "ppo.launches"}


def test_one_traced_update_waits_once_for_one_array():
    import jax
    from repro.core.ppo import PPOAgent, PPOConfig
    cfg = PPOConfig(state_dim=3, kind="categorical_multihead", buffer_size=2)
    agent = PPOAgent(cfg, jax.random.PRNGKey(0), name="ppo1")
    for i in range(2):
        s = np.full(3, i, np.float32)
        a, lp = agent.act(jax.random.PRNGKey(i), s)
        agent.store(s, a, lp, float(i))
    tracer = Tracer()
    obs_trace.enable(tracer)
    try:
        assert agent.maybe_update() is not None
    finally:
        obs_trace.disable()
    assert len(_wall_spans(tracer, "ppo.sync")) == 1
    assert tracer.counts == {"ppo.syncs": 1, "ppo.launches": 1}


def test_traced_latency_rounds_match_untraced_ones(traced_latency_rounds):
    import jax
    srv, _, plain = traced_latency_rounds
    assert [dataclasses.asdict(r) for r in srv.history] == \
        [dataclasses.asdict(r) for r in plain.history]
    assert all(r.rl_diag is None for r in srv.history)
    for a, b in ((srv.allocator.agent, plain.allocator.agent),
                 (srv.intensity.agent, plain.intensity.agent)):
        la, lb = jax.tree_util.tree_leaves(a.params), \
            jax.tree_util.tree_leaves(b.params)
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            assert np.asarray(x).tobytes() == np.asarray(y).tobytes()


def test_tracing_alone_runs_no_rl_diagnostics(monkeypatch):
    import repro.obs.rl as obs_rl

    def refuse(server):
        raise AssertionError("wave_diagnostics ran without collect_rl_diag")
    monkeypatch.setattr(obs_rl, "wave_diagnostics", refuse)
    tracer = Tracer()
    obs_trace.enable(tracer)
    try:
        srv = fresh_server()
        assert srv.collect_rl_diag is False
        srv.pretrain_rl(2)
    finally:
        obs_trace.disable()
    assert not [e for e in tracer.events if e["name"].startswith("rl.")]
    assert all(r.rl_diag is None for r in srv.history)


def test_collected_diagnostics_count_their_entropy_forwards():
    tracer = Tracer()
    obs_trace.enable(tracer)
    try:
        srv = fresh_server()
        srv.collect_rl_diag = True
        srv.run_round(latency_only=True)
    finally:
        obs_trace.disable()
    # 2 for the key split, one per act, one entropy forward per agent
    assert tracer.counts["ppo.launches"] == 2 + 2 + 2
    assert tracer.counts["ppo.syncs"] == 2 * 2 + 2


def test_ppo_programs_compile_under_stable_names():
    import jax
    import jax.numpy as jnp
    from repro.obs.rl import _entropy_fn
    srv = fresh_server()
    agent = srv.allocator.agent
    k = agent.cfg.state_dim
    state = jnp.zeros((k,), jnp.float32)
    act = agent._act.lower(agent.params, jax.random.PRNGKey(0), state,
                           False).as_text()
    assert "jit_ppo_act" in act.splitlines()[0]
    B = agent.cfg.buffer_size
    batch = {"state": jnp.zeros((B, k)), "action": jnp.zeros((B, k),
                                                             jnp.int32),
             "logprob": jnp.zeros((B,)), "reward": jnp.zeros((B,))}
    upd = agent._update.lower(agent.params, agent.opt_state,
                              batch).as_text()
    assert "jit_ppo_update" in upd.splitlines()[0]
    ent = _entropy_fn(agent).lower(agent.params, state).as_text()
    assert "jit_ppo_entropy" in ent.splitlines()[0]
    assert "unknown" not in act.splitlines()[0] + upd.splitlines()[0]


# --------------------------------------------------------------------- #
# fleet health: off = byte-identical, exposition parity
# --------------------------------------------------------------------- #
def test_health_off_runs_are_byte_identical():
    """Attaching a FleetHealth must not perturb the simulation: every
    output except the observational side channels (rl_diag, health) is
    byte-identical to a plain run — same discipline as the tracer pin
    above."""
    srv_a = fresh_server()
    res_a = EventScheduler(srv_a, SyncPolicy()).run(waves=3)
    srv_b = fresh_server()
    res_b = EventScheduler(srv_b, SyncPolicy(), health=True).run(waves=3)
    for a, b in zip(srv_a.history, srv_b.history):
        assert a.rl_diag is None and b.rl_diag is not None
        da, db = dataclasses.asdict(a), dataclasses.asdict(b)
        da.pop("rl_diag"), db.pop("rl_diag")
        assert da == db
    da, db = dataclasses.asdict(res_a), dataclasses.asdict(res_b)
    assert da.pop("health") is None and db.pop("health") is not None
    assert da == db
    assert res_a.sim_time == res_b.sim_time


def test_prometheus_matches_dump_for_deterministic_counters():
    """Every deterministic ServiceMetrics counter must appear with the
    same value in the Prometheus exposition and in the dump()/snapshot
    surface — one stream, two serializations."""
    from repro.obs.export import parse_prometheus_text
    m = _exercised_metrics()
    parsed = parse_prometheus_text(m.prometheus())
    counts = parsed["hapfl_service_counts_total"]
    for key, v in m.deterministic_counts().items():
        assert counts[(("key", key),)] == float(v), key
    snap = m.snapshot()
    assert parsed["hapfl_service_up_bytes"][()] == m.up_bytes
    assert parsed["hapfl_service_down_bytes"][()] == m.down_bytes
    stal = parsed["hapfl_service_staleness_bucket"]
    assert stal[(("le", "+Inf"),)] == \
        sum(int(v) for v in snap["staleness_hist"].values())
    # exposing twice is byte-stable (scrape determinism)
    assert m.prometheus() == m.prometheus()
