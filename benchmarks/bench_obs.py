"""Observability smoke + overhead bench (DESIGN.md §16), emitted to
artifacts/bench/obs_trace_quick.json (the Chrome trace itself) and
artifacts/bench/obs_summary_quick.json.

Two measurements:

  1. Schema smoke — run one small event-driven simulation (comm links +
     codec + real PPO agents, so every instrumented layer fires) with
     tracing enabled, export the Chrome trace-event JSON, and assert the
     exporter's invariants via `validate_chrome_trace` plus the
     HAPFL-specific expectations: both clock tracks present, nested
     wall spans (sim.dispatch > server.plan_wave, codec.encode), virtual
     wave-barrier spans carrying the assess/local/comm/barrier breakdown,
     per-wave RL diagnostic counters, and a `SimResult.timing` summary.
  2. Tracer overhead — the same simulation untraced vs traced,
     per-event wall cost of the instrumentation (the disabled path is
     separately pinned to be byte-identical in tests/test_obs.py).

The trace artifact loads directly at https://ui.perfetto.dev.
"""
from __future__ import annotations

import json

from benchmarks.common import BENCH_DIR, Timer, emit, save_json
from repro.fl import FLEnvironment, FLSimConfig, HAPFLServer
from repro.obs import trace as obs_trace
from repro.obs.trace import validate_chrome_trace
from repro.sim import EventScheduler, make_policy


def _build(seed: int):
    from repro.core.latency import make_comm_model
    cfg = FLSimConfig(dataset="mnist", n_clients=12, k_per_round=4,
                      n_train=240, n_test=64, batches_per_epoch=1,
                      default_epochs=4, batch_size=8,
                      max_speed_ratio=10.0, seed=seed)
    env = FLEnvironment(cfg)
    srv = HAPFLServer(env, seed=seed, codec="int8")
    # the per-wave RL diagnostic counters need their own switch: tracing
    # alone does not collect them
    srv.collect_rl_diag = True
    comm = make_comm_model(
        {s: float(c.num_params()) for s, c in env.pool.items()},
        float(env.lite_cfg.num_params()), cfg.n_clients, mean_mbps=1.0,
        seed=seed, codec="int8")
    return EventScheduler(srv, make_policy("buffered", buffer_m=2),
                          comm=comm, eval_accuracy=False)


def _run(seed: int, waves: int):
    sched = _build(seed)
    with Timer() as t:
        res = sched.run(waves=waves)
    return res, t.seconds


def main(waves: int = 8, seed: int = 0, quick: bool = True):
    # 1. untraced reference run (overhead baseline + jit warm cache)
    _, base_s = _run(seed, waves)

    # 2. traced run, fresh tracer so the export covers exactly this sim
    tracer = obs_trace.Tracer()
    obs_trace.enable(tracer)
    try:
        res, traced_s = _run(seed, waves)
    finally:
        obs_trace.disable()

    trace_path = BENCH_DIR / "obs_trace_quick.json"
    tracer.export(trace_path)
    trace = json.loads(trace_path.read_text())
    stats = validate_chrome_trace(trace)

    # HAPFL-specific schema expectations beyond the generic invariants
    names = {ev["name"] for ev in trace["traceEvents"]}
    required = ("sim.dispatch", "server.plan_wave", "server.train_wave",
                "server.feedback_wave", "server.apply_updates",
                "codec.encode", "codec.decode", "wave_barrier", "arrival",
                "sim.load", "rl.ppo1", "rl.ppo2")
    missing = [n for n in required if n not in names]
    if missing:
        raise AssertionError(f"trace is missing expected events: {missing}")
    if stats["pids"] != [1, 2]:
        raise AssertionError(f"expected wall+virtual tracks, got pids="
                             f"{stats['pids']}")
    if res.timing is None or res.timing["n_waves"] < 1:
        raise AssertionError(f"SimResult.timing not populated: {res.timing}")

    n_ev = max(res.n_events, 1)
    summary = {
        "waves": res.n_waves, "sim_events": res.n_events,
        "trace_events": stats["n_events"], "spans": stats["n_spans"],
        "counters": stats["n_counters"], "instants": stats["n_instants"],
        "tracks": len(stats["tracks"]),
        "untraced_wall_s": round(base_s, 3),
        "traced_wall_s": round(traced_s, 3),
        "overhead_us_per_event": round((traced_s - base_s) * 1e6 / n_ev, 1),
        "timing": res.timing,
        "trace_artifact": trace_path.name,
    }
    save_json("obs_summary_quick", summary)
    emit("obs_trace_schema", traced_s * 1e6 / n_ev,
         f"events={stats['n_events']}_spans={stats['n_spans']}"
         f"_tracks={len(stats['tracks'])}_ok")
    emit("obs_tracer_overhead", abs(traced_s - base_s) * 1e6 / n_ev,
         f"untraced={summary['untraced_wall_s']}s"
         f"_traced={summary['traced_wall_s']}s")
    return summary


if __name__ == "__main__":
    main()
