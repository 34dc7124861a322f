"""Benchmark harness — one entry per paper table/figure (+ roofline/kernels).

Prints ``name,us_per_call,derived`` CSV rows; JSON/CSV artifacts land in
artifacts/bench/. Budget knobs keep the default full run CPU-tractable;
--quick shrinks everything for smoke validation.

  fig2/fig3   bench_rl          PPO reward curves
  fig4-21     bench_accuracy    accuracy/loss vs FedAvg/FedProx (+Tab III/IV)
  (ours)      bench_accuracy    cross_size: group vs nested aggregation
  fig22/23    bench_latency     straggling latency + overall training time
  (ours)      bench_comm        update codecs x scheduling policies
  (ours)      bench_serve       parameter-service load (updates/sec, p99)
  (ours)      bench_population  100k-client SoA simulation (events/sec, mem)
  fig24       bench_scalability 20/100-client model-allocation scaling
  fig25       bench_ablation    fixed-size / fixed-intensity ablations
  (ours)      bench_mesh        sharded engine rounds/sec vs device count
  (ours)      bench_roofline    dry-run roofline table
  (ours)      bench_kernels     kernel traffic models / CPU timings
  (ours)      bench_obs         traced sim/service run -> Perfetto trace
                                (Chrome trace-event schema smoke) + tracer
                                overhead
  (ours)      bench_health      fleet health analytics: straggler phase
                                attribution + drift under churn, service
                                SLO burn rates -> fleet_health.{md,json}
"""
from __future__ import annotations

import argparse
import sys
import traceback

from repro.launch.compile_cache import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny budgets (CI smoke)")
    ap.add_argument("--only", default="",
                    help="comma list: rl,accuracy,cross_size,latency,comm,"
                         "serve,population,mesh,scalability,ablation,"
                         "roofline,kernels,obs,health")
    ap.add_argument("--datasets", default="mnist",
                    help="comma list for accuracy bench")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record a dual-clock span trace across the "
                         "selected benches and write Chrome trace-event "
                         "JSON (open at https://ui.perfetto.dev)")
    ap.add_argument("--health-report", default=None, metavar="OUT.md",
                    help="run the fleet health bench (even when absent "
                         "from --only) and write its report to OUT.md "
                         "(+ .json sibling) instead of artifacts/bench/"
                         "fleet_health[_quick].md")
    args = ap.parse_args()
    use_compile_cache()
    only = set(args.only.split(",")) if args.only else None
    if args.health_report and only is not None:
        only.add("health")         # --health-report implies the bench
    q = args.quick

    tracer = None
    if args.trace:
        from repro.obs import trace as obs_trace
        tracer = obs_trace.enable()

    def want(name):
        return only is None or name in only

    print("name,us_per_call,derived")
    failures = []

    def run(name, fn):
        if not want(name):
            return
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failures.append(name)

    if want("rl"):
        from benchmarks import bench_rl
        run("rl", lambda: bench_rl.main(rounds=300 if q else 2000))
    if want("latency"):
        from benchmarks import bench_latency
        run("latency", lambda: bench_latency.main(
            datasets=("mnist",) if q else ("mnist", "cifar10", "imagenet10"),
            warmup=300 if q else 2000, eval_rounds=50 if q else 200,
            mode_updates=72 if q else 150))
    if want("accuracy"):
        from benchmarks import bench_accuracy
        for ds in args.datasets.split(","):
            run("accuracy", lambda ds=ds: bench_accuracy.main(
                dataset=ds, rounds=6 if q else 25,
                warmup=200 if q else 1000,
                n_train=800 if q else 2000,
                default_epochs=6 if q else 10))
    if want("cross_size"):
        from benchmarks import bench_accuracy
        # quick mode writes cross_size_quick.json: the committed
        # artifacts/bench/cross_size.json is the full 10/50-client record
        # and must not be clobbered by a smoke run
        run("cross_size", lambda: bench_accuracy.run_cross_size_comparison(
            cohorts=(10,) if q else (10, 50), rounds=4 if q else 10,
            n_train=800 if q else 2000, n_test=200 if q else 400,
            default_epochs=4 if q else 8,
            artifact_name="cross_size_quick" if q else "cross_size"))
    if want("comm"):
        from benchmarks import bench_comm
        # quick mode writes comm_modes_quick.json: the committed
        # artifacts/bench/comm_modes.json is the full-budget codec sweep
        # and must not be clobbered by a smoke run (same as cross_size)
        run("comm", lambda: bench_comm.main(
            max_updates=24 if q else 200,
            codecs=(({"name": "identity"},
                     {"name": "topk+int8", "ratio": 0.08, "dense_min": 256})
                    if q else bench_comm.CODECS),
            artifact_name="comm_modes_quick" if q else "comm_modes"))
    if want("serve"):
        from benchmarks import bench_serve
        # quick mode writes serve_load_quick.json: the committed
        # artifacts/bench/serve_load.json is the full-trace service load
        # record and must not be clobbered by a smoke run
        run("serve", lambda: bench_serve.main(
            n_events=150 if q else 1500,
            n_clients=16 if q else 32,
            k_per_round=4 if q else 8,
            checkpoint_every=10 if q else 25,
            artifact_name="serve_load_quick" if q else "serve_load"))
    if want("population"):
        from benchmarks import bench_population
        # quick mode writes population_quick.json (1k/10k): the committed
        # artifacts/bench/population.json is the full 1k/10k/100k record
        # and must not be clobbered by a smoke run
        run("population", lambda: bench_population.main(
            populations=(1_000, 10_000) if q else (1_000, 10_000, 100_000),
            waves=20 if q else 60,
            artifact_name="population_quick" if q else "population"))
    if want("mesh"):
        from benchmarks import bench_mesh
        # quick mode writes mesh_scaling_quick.json: the committed
        # artifacts/bench/mesh_scaling.json is the full 64-client curve
        # and must not be clobbered by a smoke run. Each device count is
        # its own subprocess (XLA fixes the host device count at init).
        run("mesh", lambda: bench_mesh.main(
            device_counts=(1, 2, 4),
            n_clients=16 if q else 64, rounds=2 if q else 3,
            kd_rows=128 if q else 512, kd_vocab=512 if q else 2048,
            artifact_name="mesh_scaling_quick" if q else "mesh_scaling"))
    if want("scalability"):
        from benchmarks import bench_scalability
        run("scalability", lambda: bench_scalability.main(
            warmup=300 if q else 4000, eval_rounds=50 if q else 200,
            engine_rounds=2 if q else 3,
            engine_cohorts=(10, 50) if q else (10, 50, 100)))
    if want("ablation"):
        from benchmarks import bench_ablation
        run("ablation", lambda: bench_ablation.main(
            warmup=300 if q else 4000, eval_rounds=50 if q else 200))
    if want("roofline"):
        from benchmarks import bench_roofline
        run("roofline", bench_roofline.main)
    if want("kernels"):
        from benchmarks import bench_kernels
        run("kernels", bench_kernels.main)
    if want("obs"):
        from benchmarks import bench_obs
        run("obs", lambda: bench_obs.main(quick=q))
    if want("health"):
        from benchmarks import bench_health
        # quick mode writes fleet_health_quick.{md,json}: the committed
        # artifacts/bench/fleet_health.{md,json} is the full-budget
        # fleet health report and must not be clobbered by a smoke run
        run("health", lambda: bench_health.main(
            waves=10 if q else 30,
            n_clients=16 if q else 24,
            n_events=150 if q else 600,
            service_clients=16 if q else 32,
            k_per_round=4 if q else 8,
            artifact_name="fleet_health_quick" if q else "fleet_health",
            out_md=args.health_report))

    if tracer is not None:
        tracer.export(args.trace)
        print(f"# trace ({len(tracer.events)} events) -> {args.trace}",
              file=sys.stderr)

    if failures:
        print(f"# FAILED benches: {failures}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
