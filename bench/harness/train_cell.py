"""A training cell: HAPFL rounds of real mutual-KD training.

Set-up builds the fleet and the server, draws the batches and the initial
globals from the seed, runs the traffic's latency-only RL rounds, warms
every cohort, evaluation and aggregation shape the window will reach, and
drives the first training round through `run_round`, keeping what it read
and produced for the check. The window then runs `run_round` until its
time is up; each round ends with the globals on the device.

The check replays that first round with the plain float32 reference of
the config's model family (the module its `reference` names): every client from the same initial globals on
the same batches, the aggregation of the reference's clients, and, where
the round evaluates, the reference's accuracies.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import compare, device, fleet, spec
from bench.harness import trace as trace_mod


class Capture:
    """What the first training round read and produced."""

    def __init__(self):
        self.on = False
        self.batches: Dict[int, tuple] = {}
        self.trained: Dict[int, dict] = {}
        self.acc: Dict[tuple, float] = {}

    def prefetch(self, args, kw, out):
        if self.on:
            xs, ys, _ = out
            for i, (c, n) in enumerate(zip(args[0], args[1])):
                self.batches[int(c)] = (np.array(xs[i, :n]),
                                        np.array(ys[i, :n]))

    def cohort(self, args, kw, out):
        if self.on:
            for c, p in zip(args[0], out):
                self.trained[int(c)] = p

    def evaluation(self, args, kw, out):
        if not self.on:
            return
        name = args[1].name
        which = name.rsplit("-", 1)[1]
        if len(args) == 3:      # client_test_accuracy(params, cfg, client)
            self.acc[("client", "lite" if which == "lite" else "local",
                      int(args[2]))] = out
        else:                   # test_accuracy(params, cfg)
            self.acc[("global", which)] = out


class TrainingRun:
    def __init__(self, cell, seed: int, traced: bool = False):
        self.cell, self.seed = cell, seed
        self.config, self.traffic = cell.config, cell.traffic
        self.bpe = self.config["batches_per_epoch"]
        self.evals = bool(self.traffic["eval_accuracy"])
        self.tracer = None
        if traced:
            from repro.obs import trace as obs_trace
            self.tracer = obs_trace.enable()
        self.failed = 0

    # -------------------------------------------------------------- #
    def setup(self, warm: bool = True) -> None:
        config, traffic = self.config, self.traffic
        rl_rounds = int(traffic["rl_warmup_rounds"])
        env, srv, shadow_env, server_seed = fleet.build(config, traffic,
                                                        self.seed)
        self.w0 = fleet.reseed(env, srv, config, self.seed)
        fleet.note("fleet and server built")
        self.env, self.srv = env, srv
        self.data = {"x_train": env.data["x_train"],
                     "y_train": env.data["y_train"],
                     "x_test": env.data["x_test"],
                     "y_test": env.data["y_test"], "parts": env.partitions}
        self.probe, self.cap = fleet.Probe(), Capture()
        fleet.watch_training(self.probe, env, srv)
        self.probe.hooks.update({"bench.prefetch": self.cap.prefetch,
                                 "bench.cohort": self._cohort_done,
                                 "bench.eval": self.cap.evaluation})
        horizon = int(traffic["plan_horizon"])
        plans = fleet.plan_ahead(shadow_env, server_seed,
                                 rl_rounds + 1 + horizon)
        hist = []
        for _ in range(rl_rounds):
            r = srv.run_round(latency_only=True)
            hist.append((r.clients, r.sizes, r.intensities))
        if hist != plans[:rl_rounds]:
            raise RuntimeError("the latency-only twin planned differently "
                               "from the server")
        self.shapes = fleet.cohort_groups(
            plans[rl_rounds:], self.bpe, lambda c: env.loaders[c].batch_size)
        fleet.note(f"RL warm-up done; {len(self.shapes)} cohort shapes ahead")
        if warm:
            fleet.warm_cohorts(srv, self.shapes)
            fleet.note("cohort shapes warm")
            if self.evals:
                fleet.warm_eval(env, srv, config)
                fleet.note("evaluation shapes warm")
            fleet.warm_aggregation(srv)
        # the first training round, kept for the check
        self.cap.on = True
        self.failed = 0
        rec = srv.run_round(eval_accuracy=self.evals)
        jax.block_until_ready((srv.global_by_size, srv.lite_params))
        self.cap.on = False
        self.first = {
            "plan": (rec.clients, rec.sizes, rec.intensities),
            "after": jax.device_get({"lite": srv.lite_params,
                                     **srv.global_by_size})}
        self.probe.reset()
        if self.tracer is not None:
            self.tracer.clear()
        fleet.note("first training round done")

    def _cohort_done(self, args, kw, out):
        self.cap.cohort(args, kw, out)
        self.failed += sum(1 for p in out
                           if p is None or not compare.finite(p))

    # -------------------------------------------------------------- #
    def window(self, seconds: float, meter, trace_dir=None) -> Dict:
        """Rounds until `seconds` have passed (the last one counts whole),
        or the traffic's `trace_rounds` under the profiler."""
        srv, config = self.srv, self.config
        fam = spec.family(config)
        max_rounds = (int(self.traffic["trace_rounds"]) if trace_dir
                      else int(self.traffic["plan_horizon"]))
        self.failed = 0
        walls: List[float] = []
        recs = []
        snap = meter.snapshot()
        t_trace = None
        if trace_dir is not None:
            self.probe.recording = True
            t_trace = trace_mod.start(trace_dir)
        t_window = time.perf_counter()
        while len(walls) < max_rounds and (
                trace_dir is not None
                or time.perf_counter() - t_window < seconds):
            t0 = time.perf_counter()
            rec = srv.run_round(eval_accuracy=self.evals)
            jax.block_until_ready((srv.global_by_size, srv.lite_params))
            t1 = time.perf_counter()
            self.probe.mark("bench.round", t0, t1)
            walls.append(t1 - t0)
            recs.append(rec)
        window_s = time.perf_counter() - t_window
        if trace_dir is not None:
            jax.profiler.stop_trace()
            self.probe.recording = False
        bpe = self.bpe
        work = [(s, int(t) * bpe) for r in recs
                for s, t in zip(r.sizes, r.intensities)]
        return {
            "walls": walls, "attempted": len(work), "failed": self.failed,
            "host": list(self.probe.intervals), "t_trace": t_trace,
            "layer": {
                "rounds": len(walls), "window_s": window_s,
                "spans": fleet.span_totals(self.tracer),
                "wrappers": dict(self.probe.seconds),
                "compiles": meter.since(snap)["compiles"],
                "real_steps": sum(n for _, n in work),
                "real_flops": sum(n * fam.step_flops(config, s)
                                  for s, n in work),
                "real_bytes": sum(n * fam.step_bytes(config, s)
                                  for s, n in work),
                "shapes_warmed": len(self.shapes)}}

    def release(self) -> None:
        """Drop the program's state, so the reference runs without it."""
        fleet.note("window done")
        del self.srv, self.env, self.probe

    # -------------------------------------------------------------- #
    def reference(self, dtype=jnp.float32, keep=None) -> Dict:
        """The reference's first round; `keep="half"` leaves half of every
        batch out (a planted fault)."""
        if keep == "half":
            keep = self.config["batch_size"] // 2
        with device.reference_device(dtype):
            return reference_round(self.config, self.traffic, self.data,
                                   self.w0, self.cap.batches,
                                   self.first["plan"], dtype, keep)

    def program_outputs(self) -> Dict:
        clients = self.first["plan"][0]
        return {"trained": [self.cap.trained[int(c)] for c in clients],
                "after": self.first["after"], "accs": dict(self.cap.acc)}

    def numbers(self, outputs: Dict, ref: Dict) -> Dict:
        return numbers_against(self.first["plan"], self.w0, outputs, ref)


# ---------------------------------------------------------------------- #
# the reference round and the comparison
# ---------------------------------------------------------------------- #
def reference_round(config, traffic, data, w0, batches, plan,
                    dtype=jnp.float32, keep=None) -> Dict:
    """The reference's first round: trained clients, accuracies and the
    aggregated globals, from the initial globals and the same batches.
    `keep` uses only that many rows of each batch (a planted fault)."""
    fam = spec.family(config)
    clients, sizes, _ = plan
    trained, grads = [], []
    for c, s in zip(clients, sizes):
        xs, ys = batches[int(c)]
        p, g = fam.train_client({"local": w0[s], "lite": w0["lite"]},
                                xs, ys, config, dtype, keep)
        trained.append(p)
        grads.append(g)
    x_tr, y_tr, parts = data["x_train"], data["y_train"], data["parts"]
    ents = [fam.entropy(y_tr[parts[c]], config["n_classes"])
            for c in clients]
    accs = {}
    if traffic["eval_accuracy"]:
        for c, p in zip(clients, trained):
            for which in ("local", "lite"):
                accs[("client", which, int(c))] = fam.accuracy(
                    p[which], x_tr[parts[c]], y_tr[parts[c]], dtype)
    acc_loc = [accs.get(("client", "local", int(c)), 0.0) for c in clients]
    acc_lite = [accs.get(("client", "lite", int(c)), 0.0) for c in clients]
    lite, by_size = fam.aggregate(
        w0["lite"], {s: w0[s] for s in config["size_names"]}, sizes,
        trained, ents, acc_loc, acc_lite)
    after = {"lite": lite, **by_size}
    if traffic["eval_accuracy"]:
        for name, p in after.items():
            accs[("global", name)] = fam.accuracy(
                p, data["x_test"], data["y_test"], dtype)
    return {"trained": trained, "grads": grads, "after": after, "accs": accs}


def numbers_against(plan, w0, outputs: Dict, ref: Dict) -> Dict:
    """One round's outputs (the program's, or a control's) against the
    reference's: the worst leaf's and the median leaf's change gap over the
    trained clients and over the aggregated globals."""
    _, sizes, _ = plan
    client = [compare.change_gaps(outputs["trained"][i], ref["trained"][i],
                                  {"local": w0[s], "lite": w0["lite"]},
                                  ref["grads"][i])
              for i, s in enumerate(sizes)]
    glob = [compare.change_gaps(outputs["after"][n], ref["after"][n], w0[n])
            for n in ["lite"] + sorted(set(sizes))]
    out = {"client_change_gap": max(float(np.nanmax(g)) for g in client),
           "client_median_gap": max(float(np.nanmedian(g)) for g in client),
           "global_change_gap": max(float(np.nanmax(g)) for g in glob),
           "global_median_gap": max(float(np.nanmedian(g)) for g in glob)}
    if ref["accs"]:
        out["accuracy_gap"] = max(abs(outputs["accs"][key] - v)
                                  for key, v in ref["accs"].items())
    return out


def client_detail(plan, w0, outputs: Dict, ref: Dict) -> list:
    """Per client: size, steps, worst leaf (index in the {local, lite}
    leaves) and its gap, median gap."""
    clients, sizes, taus = plan
    out = []
    for i, (c, s, t) in enumerate(zip(clients, sizes, taus)):
        g = compare.change_gaps(outputs["trained"][i], ref["trained"][i],
                                {"local": w0[s], "lite": w0["lite"]},
                                ref["grads"][i])
        out.append([int(c), s, int(t), int(np.nanargmax(g)),
                    round(float(np.nanmax(g)), 4),
                    round(float(np.nanmedian(g)), 4)])
    return out

