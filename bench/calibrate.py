#!/usr/bin/env python3
"""Readings the limits of `correct` are set from (not part of a run).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--controls 3] [--both-devices]

For each seed, in one process: the cell's set-up through its first checked
steps (the window's own `run_round`, at the cell's sizes), then the numbers
`correct` compares for the program against the float32 reference and, on
the first `--controls` seeds, for the control (the reference in bfloat16)
and for the reference with half of every batch left out. A training cell
adds, on those seeds, two faults planted in the program's outputs of that
round: every client's state left unchanged, and one answer altered (the
first row of the last matrix of each client's local model, +0.05). A
training cell compares them all in the run's own walk, one client at a
time. `--both-devices` also compares the float32 reference on the device
the config's `reference_device` does not name (host or chip) against it,
as `reference_<device>`, with the seconds of each. One JSON line per
seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def unchanged(run):
    """The round with no client moved from its start, and the initial
    globals as its aggregation."""
    from bench.harness import train_cell
    return train_cell.Outputs("state_unchanged", run.first.start,
                              run.first.w0, run.cap.acc)


def altered(run):
    """The program's round with each client's answer altered where the
    cohort program produces it."""
    import jax
    import numpy as np
    from bench.harness import train_cell
    prog = run.program()

    def alter(tree):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        i = max(k for k, x in enumerate(leaves) if np.ndim(x) >= 2)
        leaves[i] = np.array(leaves[i])
        leaves[i][0] += 0.05
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def client(i):
        t = prog.client(i)
        return dict(t, local=alter(t["local"]))
    return train_cell.Outputs("altered", client, prog.after(), prog.accs)


def _rl_readings(cell, seed: int, controls: bool) -> dict:
    import jax.numpy as jnp
    from bench.harness import rl_cell
    t0 = time.perf_counter()
    run = rl_cell.RLRun(cell, seed)
    run.setup()
    t_setup = time.perf_counter() - t0
    prog = run.program_outputs()
    run.release()
    t0 = time.perf_counter()
    ref = run.reference()
    out = {"seed": seed, "setup_s": t_setup,
           "reference_s": time.perf_counter() - t0,
           "program": run.numbers(prog, ref)}
    if controls:
        out["control_bf16"] = run.numbers(run.reference(jnp.bfloat16), ref)
        out["half_batch"] = run.numbers(run.reference(keep="half"), ref)
    return out


def readings(cell, seed: int, controls: bool,
             both_devices: bool = False) -> dict:
    import jax.numpy as jnp
    from bench.harness import spec, train_cell
    if cell.traffic["latency_only"]:
        return _rl_readings(cell, seed, controls)
    t0 = time.perf_counter()
    run = train_cell.TrainingRun(cell, seed)
    run.setup(warm=False)
    t_setup = time.perf_counter() - t0
    run.release()
    where = spec.reference_device(cell.config)
    try:
        sides = [run.program()]
        if controls:
            sides += [run.reference("control_bf16", jnp.bfloat16),
                      run.reference("half_batch", keep="half"),
                      unchanged(run), altered(run)]
        if both_devices:
            other = "chip" if where == "host" else "host"
            sides.append(run.reference(f"reference_{other}", where=other))
        t0 = time.perf_counter()
        res = run.readings(sides)
    finally:
        run.close()
    out = {"seed": seed, "setup_s": t_setup,
           "check_s": time.perf_counter() - t0,
           "reference_s": res["reference"]["seconds"],
           "reference_device": where,
           "clients": res["program"]["clients"]}
    for side in sides:
        out[side.name] = res[side.name]["numbers"]
        if "seconds" in res[side.name]:
            out[f"{side.name}_s"] = res[side.name]["seconds"]
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=0)
    ap.add_argument("--both-devices", action="store_true")
    args = ap.parse_args()
    from bench.harness import device, spec
    cell = spec.load_cell(args.workload)
    device.require_accelerator(cell.chips)
    device.use_cache()
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, i < args.controls,
                                  args.both_devices)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
