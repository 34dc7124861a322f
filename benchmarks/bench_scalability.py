"""Paper Fig 24 + engine throughput: scalability in two senses.

1. Paper §V.C.4 setups: (a) 10 clients / 10x disparity / 2 sizes,
   (b) 20 clients / 20x disparity / 3 sizes, (c) 100 clients / 50x / 3 sizes.
   Metric: straggling-latency reduction vs fixed-intensity FedAvg.
2. Simulation throughput (ours): sequential vs batched client-training
   engine, rounds/sec at 10/50/100-client cohorts. The batched engine
   (repro.fl.batched) wins in the dispatch-bound small-batch regime the
   IoT simulations live in; see DESIGN.md §9 for the CPU performance model.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import (Timer, emit, measure_engine_throughput,
                               save_json)
from repro.fl import BaselineRunner, FLEnvironment, FLSimConfig, HAPFLServer
from repro.sim import EventScheduler, make_policy


def reduction(cfg, warmup, eval_rounds, seed=0):
    env = FLEnvironment(cfg)
    srv = HAPFLServer(env, seed=seed)
    srv.pretrain_rl(warmup)
    h = np.mean([srv.run_round(latency_only=True).straggling
                 for _ in range(eval_rounds)])
    env2 = FLEnvironment(cfg)
    size = list(env2.pool)[0]
    f = []
    for r in range(eval_rounds):
        clients = env2.select_clients()
        times = env2.latency.local_train_times(
            env2.store, clients, r, [size] * len(clients),
            [cfg.default_epochs] * len(clients), include_lite=False)
        f.append(max(times) - min(times))
    return float(100 * (1 - h / np.mean(f)))


def engine_throughput(cohorts=(10, 50, 100), batch_sizes=(1, 4),
                      rounds: int = 3, warmup: int = 2, seed: int = 0):
    """Sequential vs batched engine rounds/sec across cohort sizes."""
    out = {}
    for n in cohorts:
        for b in batch_sizes:
            r = max(2, rounds - 1) if n >= 100 else rounds
            res = measure_engine_throughput(n, b, rounds=r, warmup=warmup,
                                            seed=seed)
            key = f"{n}c_b{b}"
            out[key] = {k: round(v, 3) for k, v in res.items()}
            emit(f"engine_throughput_{key}", 1e6 / res["batched"],
                 f"speedup={res['speedup']:.2f}x_vs_sequential")
    save_json("engine_throughput", out)
    return out


def policy_straggling(cfg, updates: int, seed: int = 0):
    """Latency-only per-scheduling-mode straggling at one paper setup —
    pure event dynamics, no CNN training, so it scales to 100 clients."""
    out = {}
    for name, kw in (("sync", {}), ("deadline", {"quantile": 0.6}),
                     ("buffered", {"buffer_m": max(2, cfg.k_per_round // 2)}),
                     ("async", {})):
        env = FLEnvironment(cfg)
        srv = HAPFLServer(env, seed=seed, use_ppo1=False, use_ppo2=False)
        sched = EventScheduler(srv, make_policy(name, **kw),
                               latency_only=True)
        res = sched.run(waves=None, max_updates=updates)
        out[name] = {"mean_straggling": round(res.mean_straggling, 3),
                     "sim_time": round(float(res.sim_time), 2),
                     "n_updates": res.n_updates,
                     "n_dropped": res.n_dropped}
    return out


def main(warmup: int = 4000, eval_rounds: int = 200, seed: int = 0,
         engine_rounds: int = 3, engine_cohorts=(10, 50, 100)):
    setups = [
        ("10c_10x_2sizes", FLSimConfig(n_clients=10, k_per_round=6,
                                       max_speed_ratio=10,
                                       size_names=("small", "large"),
                                       n_train=800, n_test=100, seed=seed)),
        ("20c_20x_3sizes", FLSimConfig(n_clients=20, k_per_round=10,
                                       max_speed_ratio=20,
                                       size_names=("small", "medium", "large"),
                                       n_train=1500, n_test=100, seed=seed)),
        ("100c_50x_3sizes", FLSimConfig(n_clients=100, k_per_round=20,
                                        max_speed_ratio=50,
                                        size_names=("small", "medium", "large"),
                                        n_train=4000, n_test=100, seed=seed)),
    ]
    out = {}
    for name, cfg in setups:
        with Timer() as t:
            # larger client pools need proportionally more PPO updates
            w = warmup * 2 if cfg.n_clients >= 100 else warmup
            red = reduction(cfg, w, eval_rounds, seed)
        out[name] = {"straggling_reduction_pct": round(red, 2),
                     "seconds": round(t.seconds, 1)}
        emit(f"fig24_scalability_{name}", t.seconds * 1e6 / eval_rounds,
             f"straggling_reduction={red:.1f}%")
        with Timer() as tm:
            modes = policy_straggling(cfg,
                                      updates=eval_rounds * cfg.k_per_round,
                                      seed=seed)
        out[name]["async_modes"] = modes
        emit(f"async_modes_{name}", tm.seconds * 1e6 / eval_rounds,
             "straggling_" + "_".join(
                 f"{m}={v['mean_straggling']:.1f}" for m, v in modes.items()))
    out["engine_throughput"] = engine_throughput(
        cohorts=engine_cohorts, rounds=engine_rounds, seed=seed)
    save_json("scalability", out)
    return out


if __name__ == "__main__":
    main()
