"""A training cell: HAPFL rounds of real mutual-KD training.

Set-up builds the fleet and the server, draws the batches and the initial
globals from the seed, runs the traffic's latency-only RL rounds, warms
every cohort, evaluation and aggregation shape the window will reach, and
drives the first training round through `run_round`, keeping what it read
and produced for the check: each client's batches, the aggregated globals,
the accuracies, and each trained client, written to the run's temporary
directory as the cohort engine hands it over. The window then runs
`run_round` until its time is up; each round ends with the globals on the
device.

The check replays that first round with the plain float32 reference of
the config's model family (the module its `reference` names), on the
host or on the chip as the config's `reference_device` says, one client
at a time in plan order: the reference trains the client from the same
initial globals on the same batches, the program's client is read back,
their change gaps are taken, and both leave memory; the reference's
client goes to disk for the fold. Once every client is done, the
reference's aggregation (Eqs. 38/39 and 5, float64) reads its clients
back as memory maps, leaf by leaf, since its softmax weights need the
whole cohort first. Host memory holds the globals and at most three
client models, whatever the number of clients. Where the round evaluates,
the reference's accuracies are compared too.
"""
from __future__ import annotations

import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import compare, device, fleet, spec
from bench.harness import trace as trace_mod
from bench.harness.spill import ClientStore


class Capture:
    """What the first training round read and produced; its trained
    clients go to `store` as they come."""

    def __init__(self, store: ClientStore):
        self.on = False
        self.batches: Dict[int, tuple] = {}
        self.store = store
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
                self.store.put(int(c), p)

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
        self.tmp = tempfile.TemporaryDirectory(prefix="bench-clients-")
        self.probe = fleet.Probe()
        self.cap = Capture(ClientStore(Path(self.tmp.name) / "program"))
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
        self.after = jax.device_get({"lite": srv.lite_params,
                                     **srv.global_by_size})
        self.first = FirstRound(
            fam=spec.family(config), config=config, evals=self.evals,
            plan=(rec.clients, rec.sizes, rec.intensities), w0=self.w0,
            batches=self.cap.batches, data=self.data)
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
    def program(self) -> "Outputs":
        """The program's first round: its clients as spilled, its globals
        and accuracies."""
        clients = self.first.plan[0]
        return Outputs("program",
                       lambda i: self.cap.store.get(int(clients[i])),
                       self.after, self.cap.acc)

    def reference(self, name: str, dtype=jnp.float32, keep=None,
                  where: str = None) -> "Reference":
        """A reference round in `dtype` on `where` (the config's
        `reference_device` by default) to compare against the float32
        one; `keep="half"` leaves half of every batch out (a planted
        fault)."""
        if keep == "half":
            keep = self.config["batch_size"] // 2
        return Reference(self.first, Path(self.tmp.name) / name, name, dtype,
                         keep, where)

    def readings(self, sides: Sequence) -> Dict[str, Dict]:
        """Each side's numbers against the float32 reference (see
        `walk`)."""
        return walk(self.first, sides, Path(self.tmp.name) / "reference")

    def check(self) -> Dict:
        """The numbers `correct` compares, for the program; the spilled
        clients are removed after."""
        try:
            return self.readings([self.program()])["program"]["numbers"]
        finally:
            self.close()

    def close(self) -> None:
        self.tmp.cleanup()


# ---------------------------------------------------------------------- #
# the check: the first round again, one client at a time
# ---------------------------------------------------------------------- #
@dataclass
class FirstRound:
    """What the check replays: the plan (clients, sizes, intensities), the
    initial globals, each client's batches, and the data the entropies and
    accuracies are taken on."""
    fam: ModuleType
    config: Dict
    evals: bool
    plan: tuple
    w0: Dict
    batches: Dict[int, tuple]
    data: Dict

    def start(self, i: int) -> Dict:
        return {"local": self.w0[self.plan[1][i]], "lite": self.w0["lite"]}


class Outputs:
    """A side of the comparison that is given: `client(i)` is the i-th
    client of the plan as trained, `after()` the aggregated globals,
    `accs` the accuracies."""

    def __init__(self, name: str, client: Callable, after: Dict,
                 accs: Dict):
        self.name, self.client, self.accs = name, client, accs
        self._after = after

    def after(self) -> Dict:
        return self._after


class Reference:
    """A round of the family's plain reference in `dtype` on `where` (the
    config's `reference_device` by default; `keep`: rows of each batch, a
    planted fault). `client(i)` trains the i-th client of the plan, keeps
    the norms of its first gradient and writes it to `directory`;
    `after()` folds the clients from there."""

    def __init__(self, first: FirstRound, directory, name: str = "reference",
                 dtype=jnp.float32, keep=None, where: str = None):
        self.first, self.name = first, name
        self.dtype, self.keep = dtype, keep
        self.where = where or spec.reference_device(first.config)
        self.store = ClientStore(directory)
        self.accs: Dict[tuple, float] = {}
        self.grad_norms = None
        self.seconds = 0.0

    def client(self, i: int):
        rd, t0 = self.first, time.perf_counter()
        c = int(rd.plan[0][i])
        xs, ys = rd.batches[c]
        with device.reference_device(self.dtype, self.where):
            trained, self.grad_norms = rd.fam.train_client(
                rd.start(i), xs, ys, rd.config, self.dtype, self.keep)
            if rd.evals:
                rows = rd.data["parts"][c]
                for which in ("local", "lite"):
                    self.accs[("client", which, c)] = rd.fam.accuracy(
                        trained[which], rd.data["x_train"][rows],
                        rd.data["y_train"][rows], self.dtype)
        self.store.put(i, trained)
        self.seconds += time.perf_counter() - t0
        return trained

    def after(self) -> Dict:
        rd, t0 = self.first, time.perf_counter()
        clients, sizes, _ = rd.plan
        y_tr, parts = rd.data["y_train"], rd.data["parts"]
        ents = [rd.fam.entropy(y_tr[parts[c]], rd.config["n_classes"])
                for c in clients]
        acc = {which: [self.accs.get(("client", which, int(c)), 0.0)
                       for c in clients] for which in ("local", "lite")}
        with device.reference_device(self.dtype, self.where):
            lite, by_size = rd.fam.aggregate(
                rd.w0["lite"], {s: rd.w0[s] for s in rd.config["size_names"]},
                sizes, [self.store.get(i, mapped=True)
                        for i in range(len(clients))],
                ents, acc["local"], acc["lite"])
            out = {"lite": lite, **by_size}
            if rd.evals:
                for name, p in out.items():
                    self.accs[("global", name)] = rd.fam.accuracy(
                        p, rd.data["x_test"], rd.data["y_test"], self.dtype)
        self.seconds += time.perf_counter() - t0
        return out


def walk(first: FirstRound, sides: Sequence, directory) -> Dict[str, Dict]:
    """Each side's first round against the float32 reference's, one client
    at a time in plan order: the reference trains the client, each side
    gives its own, the change gaps are taken and both are dropped. Then
    the aggregated globals and, where the round evaluates, the
    accuracies. Returns, per side, its `numbers` (the worst and the median
    leaf's change gap over the clients and over the globals), per client
    `clients` (client, size, intensity, worst leaf, its gap, median gap)
    and its reference's `seconds` where it has one; under "reference" the
    float32 reference's seconds."""
    ref = Reference(first, directory)
    clients, sizes, taus = first.plan
    gaps: Dict[str, List[np.ndarray]] = {s.name: [] for s in sides}
    for i in range(len(clients)):
        r = ref.client(i)
        for side in sides:
            gaps[side.name].append(compare.change_gaps(
                side.client(i), r, first.start(i), ref.grad_norms))
        del r
    ref_after = ref.after()
    models = ["lite"] + sorted(set(sizes))
    out = {"reference": {"seconds": ref.seconds}}
    for side in sides:
        after = side.after()
        glob = [compare.change_gaps(after[n], ref_after[n], first.w0[n])
                for n in models]
        del after
        client = gaps[side.name]
        numbers = {
            "client_change_gap": max(float(np.nanmax(g)) for g in client),
            "client_median_gap": max(float(np.nanmedian(g)) for g in client),
            "global_change_gap": max(float(np.nanmax(g)) for g in glob),
            "global_median_gap": max(float(np.nanmedian(g)) for g in glob)}
        if ref.accs:
            numbers["accuracy_gap"] = max(abs(side.accs[key] - v)
                                          for key, v in ref.accs.items())
        out[side.name] = {
            "numbers": numbers,
            "clients": [[int(c), s, int(t), int(np.nanargmax(g)),
                         round(float(np.nanmax(g)), 4),
                         round(float(np.nanmedian(g)), 4)]
                        for c, s, t, g in zip(clients, sizes, taus, client)]}
        if isinstance(side, Reference):
            out[side.name]["seconds"] = side.seconds
    return out
