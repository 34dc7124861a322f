#!/usr/bin/env python3
"""An account of a latency-only round by the program's own spans (not part
of a run).

    python3 bench/account.py --workload mnist.rl_k64 --seeds 1,2,3 \
        [--seconds 30] [--out account.jsonl]

For each seed, in one process: the cell's set-up, then three windows on
the same server. `off`: tracing off, for `--seconds` (what `rl_round_ms`
measures). `spans`: the program's tracer on, for `--seconds`. `traced`:
the tracer and the device profiler on, for the traffic's `trace_rounds`
rounds (what a `--trace 1` run measures). For the two traced windows it
splits the rounds' wall time by the self time of each span (per span name
and enclosing span), gives the remainder no program span covers, and the
counts per round; for `traced` also the trace's breakdown, whose idle
gaps are labelled by the program's spans as well as the harness's
wrappers. One JSON line
per seed on stdout; `--out` keeps them all.
"""
from __future__ import annotations

import argparse
import json
import shutil
import statistics
import sys
import tempfile
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def self_times(events) -> dict:
    """{(name, parent): [total s, self s, calls]} over the wall spans of a
    tracer's events; self time is the span's duration less its children's.
    """
    rows = defaultdict(lambda: [0.0, 0.0, 0])
    child = defaultdict(float)
    for ev in events:
        if ev.get("ph") != "X" or ev.get("clock") != "wall":
            continue
        row = rows[(ev["name"], ev.get("parent"))]
        row[0] += ev["dur"]
        row[2] += 1
        if ev.get("parent") is not None:
            child[ev["parent"]] += ev["dur"]
    # children are keyed by their parent's name; split a name's children
    # over its rows in proportion to each row's total
    totals = defaultdict(float)
    for (name, _), row in rows.items():
        totals[name] += row[0]
    for (name, _), row in rows.items():
        share = row[0] / totals[name] if totals[name] else 0.0
        row[1] = row[0] - child[name] * share
    return dict(rows)


def account(walls, events, counts) -> dict:
    """The rounds' wall time split by span self time, in ms per round."""
    n = len(walls)
    rows = self_times(events)
    top = sum(r[0] for (_, parent), r in rows.items() if parent is None)
    wall = sum(walls)
    per = lambda s: s / n * 1e3
    return {
        "rounds": n, "round_ms": per(wall),
        "uncovered_ms": per(wall - top),
        "uncovered_share": (wall - top) / wall if wall else None,
        "spans": sorted(([name, parent, per(r[0]), per(r[1]), r[2] / n]
                         for (name, parent), r in rows.items()),
                        key=lambda row: -row[3]),
        "counts": {k: v / n for k, v in counts.items()}}


def one_seed(cell, seed: int, seconds: float) -> dict:
    from bench.harness import device, rl_cell
    from bench.harness import trace as trace_mod
    from repro.obs import trace as obs_trace
    meter = device.CompileMeter()
    run = rl_cell.RLRun(cell, seed)
    run.setup()
    off = run.window(seconds, meter)["walls"]
    out = {"seed": seed, "off": {"rounds": len(off),
                                 "round_ms": statistics.fmean(off) * 1e3}}
    run.tracer = obs_trace.enable()
    try:
        run.tracer.clear()
        res = run.window(seconds, meter)
        out["spans"] = account(res["walls"], run.tracer.events,
                               run.tracer.counts)
        run.tracer.clear()
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            res = run.window(0.0, meter, trace_dir)
            # the program's spans nest inside the harness's wrappers, so
            # an idle gap takes the innermost, finer label
            host = res["host"] + run.tracer.host_intervals()
            red = trace_mod.reduce(trace_mod.events_from_file(
                trace_mod.find_trace(trace_dir), host, res["t_trace"]))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        out["traced"] = dict(account(res["walls"], run.tracer.events,
                                     run.tracer.counts),
                             busy_s=red["busy_s"], window_s=red["window_s"],
                             modules=red["modules"],
                             breakdown=red["breakdown"])
    finally:
        obs_trace.disable()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from bench.harness import device, spec
    cell = spec.load_cell(args.workload)
    if not cell.traffic["latency_only"]:
        print("bench: the account covers latency-only cells",
              file=sys.stderr)
        return 2
    device.require_accelerator(cell.chips)
    device.use_cache()
    lines = []
    for seed in (int(s) for s in args.seeds.split(",")):
        lines.append(one_seed(cell, seed, args.seconds))
        print(json.dumps(lines[-1]), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text("".join(json.dumps(x) + "\n"
                                          for x in lines))
    return 0


if __name__ == "__main__":
    sys.exit(main())
