"""Mesh-scaling benchmark: sharded cohort engine rounds/sec vs device count.

On the CPU backend the XLA host-device count is fixed at backend
initialization, so each device count runs in its own **subprocess** with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` exported before
jax imports. On an accelerator a chip belongs to one process (a parent
that has touched JAX holds it), so every count runs in-process over a
``make_debug_mesh(n)`` of the first n devices. The parent collects one
JSON row per count and writes
``artifacts/bench/mesh_scaling.json`` (quick runs write
``mesh_scaling_quick.json``, gitignored, so the committed full-budget
record is never clobbered — same convention as the other benches).

Per device count the worker measures, RL frozen (fixed size/intensity
assignment so every mesh trains the identical workload):

  - steady-state cohort rounds/sec of `ShardedClientEngine.train_cohort`
    on a 64-client mixed-size cohort (one warmup round absorbs jit);
  - the per-shard `sharded_kd_loss` Pallas kernel: rows/shard, wall time,
    and the HBM-traffic model bytes each shard moves (the roofline
    numbers docs/kernels.md cites);
  - a traced round (repro.obs) to confirm the sharded path emits its
    `train_cohort[...]@mesh...` spans end-to-end.

Interpretation caveat, recorded in the artifact: simulated host devices
multiplex the machine's physical cores. With fewer cores than devices
the curve *measures dispatch/partitioning overhead, not parallel
speedup* — `host.cpu_count` in the artifact says which regime produced
it (docs/sharding.md §5 reads the committed curve).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

# ------------------------------------------------------------------ #
# worker: runs under a forced host device count, prints one JSON line
# ------------------------------------------------------------------ #

def worker(devices: int, n_clients: int, rounds: int, warmup: int,
           kd_rows: int, kd_vocab: int, in_process: bool = False) -> dict:
    if not in_process:
        assert os.environ.get("XLA_FLAGS", "").find(
            f"--xla_force_host_platform_device_count={devices}") >= 0
    import jax
    import numpy as np
    from repro.fl import FLEnvironment, FLSimConfig
    from repro.fl.sharded import ShardedClientEngine
    from repro.kernels.sharded import sharded_kd_loss
    from repro.launch.mesh import make_debug_mesh
    from repro.obs import trace as obs_trace

    assert len(jax.devices()) == devices or in_process, jax.devices()
    mesh = make_debug_mesh(devices)
    cfg = FLSimConfig(dataset="mnist", n_clients=n_clients,
                      k_per_round=n_clients, batches_per_epoch=1,
                      batch_size=8, n_train=max(1200, 30 * n_clients),
                      n_test=100, size_names=("small", "large"), seed=0)
    env = FLEnvironment(cfg)
    eng = ShardedClientEngine(env, mesh=mesh)
    # frozen mixed-size ragged workload — identical at every device count
    clients = list(range(n_clients))
    sizes = [("small", "large")[i % 2] for i in clients]
    intensities = [1 + (i % 4) for i in clients]
    srv_globals = _init_globals(env)
    lite = _init_lite(env)

    def one_round():
        out = eng.train_cohort(clients, sizes, intensities, srv_globals, lite)
        return out

    for _ in range(warmup):
        one_round()
    t0 = time.perf_counter()
    for _ in range(rounds):
        one_round()
    dt = (time.perf_counter() - t0) / rounds

    # traced round: the sharded path must emit its cohort spans
    tracer = obs_trace.enable()
    one_round()
    spans = [e for e in tracer.events
             if str(e.get("name", "")).startswith("train_cohort[")]
    obs_trace.disable()

    # per-shard kd_loss kernel (interpret mode off-TPU): rows split over
    # the mesh, each device sweeps its rows' full vocab once
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (kd_rows, kd_vocab))
    y = jax.random.normal(jax.random.fold_in(key, 1), (kd_rows, kd_vocab))
    lab = jax.random.randint(jax.random.fold_in(key, 2), (kd_rows,), 0,
                             kd_vocab)
    jax.block_until_ready(sharded_kd_loss(x, y, lab, mesh))   # compile
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        jax.block_until_ready(sharded_kd_loss(x, y, lab, mesh))
    kd_us = (time.perf_counter() - t0) / reps * 1e6
    rows_per_shard = kd_rows // devices
    return {
        "devices": devices,
        "rounds_per_sec": 1.0 / dt,
        "sec_per_round": dt,
        "cohort_spans_traced": len(spans),
        "kd_loss": {
            "rows": kd_rows, "vocab": kd_vocab,
            "rows_per_shard": rows_per_shard,
            "us_per_call": kd_us,
            # fused kernel reads x and y exactly once per row (fp32)
            "fused_bytes_per_shard": 2 * rows_per_shard * kd_vocab * 4,
            "naive_bytes_per_shard": 6 * rows_per_shard * kd_vocab * 4,
        },
    }


def _init_globals(env):
    import jax
    from repro.models.cnn import init_cnn
    k = jax.random.PRNGKey(7)
    return {s: init_cnn(jax.random.fold_in(k, i), c)
            for i, (s, c) in enumerate(env.pool.items())}


def _init_lite(env):
    import jax
    from repro.models.cnn import init_cnn
    return init_cnn(jax.random.PRNGKey(8), env.lite_cfg)


# ------------------------------------------------------------------ #
# parent: one row per device count (subprocess on CPU), assemble the
# artifact
# ------------------------------------------------------------------ #

def _run_worker(devices: int, args_dict: dict) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices} "
                        + env.get("XLA_FLAGS", "")).strip()
    env["PYTHONPATH"] = "src:." + (
        ":" + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    cmd = [sys.executable, __file__, "--worker", "--devices", str(devices)]
    for k in ("clients", "rounds", "warmup", "kd_rows", "kd_vocab"):
        cmd += [f"--{k.replace('_', '-')}", str(args_dict[k])]
    res = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         cwd=Path(__file__).resolve().parents[1],
                         timeout=3600)
    if res.returncode != 0:
        raise RuntimeError(f"bench_mesh worker (devices={devices}) failed:\n"
                           f"{res.stderr[-3000:]}")
    return json.loads(res.stdout.splitlines()[-1])


def main(device_counts=(1, 2, 4), n_clients: int = 64, rounds: int = 3,
         warmup: int = 1, kd_rows: int = 512, kd_vocab: int = 2048,
         artifact_name: str = "mesh_scaling") -> dict:
    import jax
    from benchmarks.common import emit, save_json
    wargs = {"clients": n_clients, "rounds": rounds, "warmup": warmup,
             "kd_rows": kd_rows, "kd_vocab": kd_vocab}
    dev = jax.devices()[0]
    in_process = dev.platform != "cpu"
    if in_process:
        device_counts = tuple(n for n in device_counts
                              if n <= len(jax.devices()))
    rows = {}
    for n in device_counts:
        rows[str(n)] = (worker(n, n_clients, rounds, warmup, kd_rows,
                               kd_vocab, in_process=True)
                        if in_process else _run_worker(n, wargs))
        r = rows[str(n)]
        emit(f"mesh_cohort_d{n}", r["sec_per_round"] * 1e6,
             f"clients={n_clients};rounds_per_sec={r['rounds_per_sec']:.3f}")
        emit(f"mesh_kd_loss_d{n}", r["kd_loss"]["us_per_call"],
             f"rows_per_shard={r['kd_loss']['rows_per_shard']}")
    base = rows[str(device_counts[0])]["rounds_per_sec"]
    speedups = {n: rows[str(n)]["rounds_per_sec"] / base
                for n in device_counts}
    cores = os.cpu_count()
    max_d = max(device_counts)
    if in_process:
        note = (f"in-process meshes over the first n of "
                f"{len(jax.devices())} {dev.device_kind} device(s).")
    elif cores < max_d:
        note = (f"host has {cores} physical core(s) for {max_d} simulated "
                f"devices: every shard multiplexes the same core(s), so the "
                f"curve measures sharding overhead (partitioned dispatch + "
                f"result gather), not parallel speedup — flat-to-declining "
                f"by construction. On hosts with >= {max_d} cores (or real "
                f"accelerators) the shards run concurrently.")
    else:
        note = (f"host has {cores} cores >= {max_d} devices: shards run on "
                f"distinct cores and the curve reflects genuine "
                f"client-data-parallel scaling.")
    artifact = {
        "config": {"n_clients": n_clients, "rounds": rounds,
                   "warmup": warmup, "sizes": "small/large alternating",
                   "intensities": "1..4 cycling", "batch_size": 8,
                   "batches_per_epoch": 1,
                   "kd_rows": kd_rows, "kd_vocab": kd_vocab},
        "host": {"cpu_count": cores, "note": note},
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "rows": rows,
        "scaling": {
            "devices": list(device_counts),
            "rounds_per_sec": [rows[str(n)]["rounds_per_sec"]
                               for n in device_counts],
            "speedup_vs_1": {str(n): speedups[n] for n in device_counts},
        },
    }
    save_json(artifact_name, artifact)
    emit("mesh_scaling_summary", 0.0,
         ";".join(f"d{n}={speedups[n]:.2f}x" for n in device_counts))
    return artifact


def _cli():
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--clients", type=int, default=64)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--kd-rows", type=int, default=512)
    ap.add_argument("--kd-vocab", type=int, default=2048)
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    if args.worker:
        out = worker(args.devices, args.clients, args.rounds, args.warmup,
                     args.kd_rows, args.kd_vocab)
        print(json.dumps(out))
        return
    if args.quick:
        main(device_counts=(1, 2, 4), n_clients=16, rounds=2, warmup=1,
             kd_rows=128, kd_vocab=512, artifact_name="mesh_scaling_quick")
    else:
        main()


if __name__ == "__main__":
    _cli()
