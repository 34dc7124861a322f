"""One run of one cell; prints the result as the last line of stdout.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

With `--trace 0` the result holds the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, read from a profiler trace of the
traffic's `trace_rounds` rounds. Exits non-zero, printing no result, when
JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import shutil
import statistics
import sys
import tempfile
import time
from typing import Dict

from bench.harness import compare, device, fleet, spec
from bench.harness import trace as trace_mod


def _round_mean(res, scale):
    return statistics.fmean(res["walls"]) * scale


# end-to-end metrics: name -> value from a run's result and set-up time
END_TO_END = {
    "setup_s": lambda res, setup_s: setup_s,
    "round_s": lambda res, setup_s: _round_mean(res, 1.0),
    "rl_round_ms": lambda res, setup_s: _round_mean(res, 1e3),
}


def cell_run(cell, seed: int, traced: bool = False):
    """The run object of the cell's kind: latency-only rounds or training
    rounds."""
    from bench.harness import rl_cell, train_cell
    kind = rl_cell.RLRun if cell.traffic["latency_only"] \
        else train_cell.TrainingRun
    return kind(cell, seed, traced)


def execute(cell, seed: int, seconds: float, traced: bool, t_start: float,
            devs) -> Dict:
    """Set-up, window, check and metrics of one run on `devs`."""
    info = device.device_info(devs)
    peak = device.peaks(info["kind"]) if info["platform"] == "tpu" else None
    meter = device.CompileMeter()
    run = cell_run(cell, seed, traced)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    try:
        run.setup()
        setup_s = time.perf_counter() - t_start
        res = run.window(seconds, meter, trace_dir)
        mem = device.memory_peak_bytes(devs)
        run.release()
        t_check = time.perf_counter()
        with fleet.HostMemory() as host:
            numbers = run.check()
        fleet.note(f"check done in {time.perf_counter() - t_check:.1f} s; "
                   f"host memory {host.start:.2f} GiB before it, at most "
                   f"{host.peak:.2f} GiB during it")
        metrics, extra = {}, {}
        if traced:
            red = trace_mod.reduce(trace_mod.events_from_file(
                trace_mod.find_trace(trace_dir), res["host"], res["t_trace"]))
            ctx = dict(res["layer"], trace=red, peaks=peak)
            for m in cell.per_layer:
                v = cell.readers[m["name"]](ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            extra = {"busy_s": red["busy_s"], "window_s": red["window_s"]}
        else:
            for m in cell.end_to_end:
                metrics[m["name"]] = {
                    "value": END_TO_END[m["name"]](res, setup_s),
                    "unit": m["unit"]}
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    judged = compare.verdict(numbers, cell.limits["limits"])
    out = {"correct": judged["correct"], "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics,
           "device": dict(info, memory_peak_bytes=mem, **extra)}
    if traced:
        out["breakdown"] = red["breakdown"]
    out["compared"] = judged["compared"]
    return out


def main(argv=None, t_start: float = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.load_cell(args.workload)
        devs = device.require_accelerator(cell.chips)
    except (spec.SpecError, device.NoAccelerator) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    device.use_cache()
    out = execute(cell, args.seed, args.seconds, bool(args.trace), t_start,
                  devs)
    for name, c in out["compared"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0
