#!/usr/bin/env python3
"""Readings the limits of `correct` are set from (not part of a run).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--controls 3]

For each seed, in one process: the cell's set-up through its first checked
steps (the window's own `run_round`, at the cell's sizes), then the numbers
`correct` compares for the program against the float32 reference and, on
the first `--controls` seeds, for the control (the reference in bfloat16)
and for the reference with half of every batch left out. A training cell
adds, on those seeds, two faults planted in the program's outputs of that
round: every client's state left unchanged, and one answer altered (the
first row of the last matrix of each client's local model, +0.05). One
JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def unchanged(run, prog: dict) -> dict:
    """The round's outputs had no client moved from its start."""
    _, sizes, _ = run.first["plan"]
    return dict(prog, trained=[{"local": run.w0[s], "lite": run.w0["lite"]}
                               for s in sizes], after=dict(run.w0))


def altered(prog: dict) -> dict:
    """The round's outputs with each client's answer altered where the
    cohort program produces it."""
    import jax
    import numpy as np

    def alter(tree):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        i = max(k for k, x in enumerate(leaves) if np.ndim(x) >= 2)
        leaves[i] = np.array(leaves[i])
        leaves[i][0] += 0.05
        return jax.tree_util.tree_unflatten(treedef, leaves)
    return dict(prog, trained=[dict(t, local=alter(t["local"]))
                               for t in prog["trained"]])


def readings(cell, seed: int, controls: bool) -> dict:
    import jax.numpy as jnp
    from bench.harness import rl_cell, train_cell
    t0 = time.perf_counter()
    if cell.traffic["latency_only"]:
        run = rl_cell.RLRun(cell, seed)
        run.setup()
    else:
        run = train_cell.TrainingRun(cell, seed)
        run.setup(warm=False)
    t_setup = time.perf_counter() - t0
    prog = run.program_outputs()
    run.release()
    t0 = time.perf_counter()
    ref = run.reference()
    out = {"seed": seed, "setup_s": t_setup,
           "reference_s": time.perf_counter() - t0,
           "program": run.numbers(prog, ref)}
    if not cell.traffic["latency_only"]:
        out["clients"] = train_cell.client_detail(run.first["plan"], run.w0,
                                                  prog, ref)
    if controls:
        out["control_bf16"] = run.numbers(run.reference(jnp.bfloat16), ref)
        out["half_batch"] = run.numbers(run.reference(keep="half"), ref)
        if not cell.traffic["latency_only"]:
            out["state_unchanged"] = run.numbers(unchanged(run, prog), ref)
            out["altered"] = run.numbers(altered(prog), ref)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=0)
    args = ap.parse_args()
    from bench.harness import device, spec
    cell = spec.load_cell(args.workload)
    device.require_accelerator(cell.chips)
    device.use_cache()
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, i < args.controls)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
