"""An RL cell: latency-only rounds, the PPO pre-training that every HAPFL
run pays before training (what `pretrain_rl` calls).

Set-up builds the fleet and the server from the seed and drives the
traffic's first `check_rounds` rounds through `run_round(latency_only=
True)`, logging each agent's acts and updates. The window then runs such
rounds until its time is up.

The check replays the log with the plain reference (bench/references/
ppo.py): from the agents' initial weights, derived from the server's seed,
the log-probability of every logged action and the parameters after every
logged update.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import compare, device, fleet, spec
from bench.harness import trace as trace_mod
from bench.references import ppo as ref_ppo


class RLRun:
    def __init__(self, cell, seed: int, traced: bool = False):
        self.cell, self.seed = cell, seed
        self.config, self.traffic = cell.config, cell.traffic
        self.tracer = None
        if traced:
            from repro.obs import trace as obs_trace
            self.tracer = obs_trace.enable()
        self.on = False
        self.log: Dict[str, List] = {"ppo1": [], "ppo2": []}

    def _watch(self, name: str, agent) -> None:
        def acted(args, kw, out):
            if self.on:
                self.log[name].append(("act", np.asarray(args[1], np.float32),
                                       np.asarray(out[0]), float(out[1])))

        def updated(args, kw, out):
            if self.on and out is not None:
                b = agent.cfg.buffer_size
                self.log[name].append(
                    ("update", np.asarray(agent.reward_history[-b:],
                                          np.float32),
                     jax.device_get(agent.params)))
        self.probe.wrap(agent, "act", f"bench.{name}.act")
        self.probe.wrap(agent, "maybe_update", f"bench.{name}.update")
        self.probe.hooks[f"bench.{name}.act"] = acted
        self.probe.hooks[f"bench.{name}.update"] = updated

    def setup(self) -> None:
        env, srv, _, self.server_seed = fleet.build(self.config, self.traffic,
                                                    self.seed, shadow=False)
        self.env, self.srv = env, srv
        self.k, self.n_sizes = env.cfg.k_per_round, len(env.pool)
        self.probe = fleet.Probe()
        fleet.watch_server(self.probe, srv)
        self._watch("ppo1", srv.allocator.agent)
        self._watch("ppo2", srv.intensity.agent)
        self.on = True
        for _ in range(int(self.traffic["check_rounds"])):
            srv.run_round(latency_only=True)
        self.on = False
        self.probe.reset()
        if self.tracer is not None:
            self.tracer.clear()

    def window(self, seconds: float, meter, trace_dir=None) -> Dict:
        srv, pool = self.srv, set(self.env.pool)
        walls: List[float] = []
        attempted = failed = 0
        snap = meter.snapshot()
        t_trace = None
        if trace_dir is not None:
            self.probe.recording = True
            t_trace = trace_mod.start(trace_dir)
        t_window = time.perf_counter()
        while (len(walls) < int(self.traffic["trace_rounds"])
               if trace_dir is not None
               else time.perf_counter() - t_window < seconds):
            t0 = time.perf_counter()
            rec = srv.run_round(latency_only=True)
            t1 = time.perf_counter()
            self.probe.mark("bench.round", t0, t1)
            walls.append(t1 - t0)
            attempted += len(rec.clients)
            failed += sum(1 for s, t, lt in zip(rec.sizes, rec.intensities,
                                                rec.local_times)
                          if s not in pool or t < 1 or not np.isfinite(lt))
        window_s = time.perf_counter() - t_window
        if trace_dir is not None:
            jax.profiler.stop_trace()
            self.probe.recording = False
        return {"walls": walls, "attempted": attempted, "failed": failed,
                "host": list(self.probe.intervals), "t_trace": t_trace,
                "layer": {"rounds": len(walls), "window_s": window_s,
                          "spans": fleet.span_totals(self.tracer),
                          "wrappers": dict(self.probe.seconds),
                          "compiles": meter.since(snap)["compiles"]}}

    def release(self) -> None:
        """Drop the program's state, so the reference runs without it."""
        fleet.note("window done")
        del self.srv, self.env, self.probe

    # -------------------------------------------------------------- #
    def program_outputs(self) -> Dict:
        return {name: {"lps": [e[3] for e in log if e[0] == "act"],
                       "params": [e[2] for e in log if e[0] == "update"]}
                for name, log in self.log.items()}

    def reference(self, dtype=jnp.float32, keep=None) -> Dict:
        """Replay the logged acts and updates from the agents' initial
        weights; `dtype` is the precision of the whole replay. `keep="half"`
        updates on half of each buffer (a planted fault)."""
        with device.reference_device(dtype,
                                     spec.reference_device(self.config)):
            return self._replay(dtype, keep)

    def _replay(self, dtype, keep) -> Dict:
        p = ref_ppo.init_agents(jax.random.PRNGKey(self.server_seed),
                                self.k, self.n_sizes)
        self.p0 = jax.device_get(p)
        out = {}
        for name, log in self.log.items():
            kind = ref_ppo.AGENTS[name]["kind"]
            q, opt = p[name], ref_ppo.init_opt(p[name])
            lps, params, grads, acts = [], [], [], []
            for ev in log:
                if ev[0] == "act":
                    _, state, action, _ = ev
                    lps.append(float(ref_ppo.logprob(
                        q, jnp.asarray(state), jnp.asarray(action),
                        kind=kind, n_sizes=self.n_sizes, dtype=dtype)))
                    acts.append((state, action))
                else:
                    b = len(ev[1])
                    rows = slice(0, max(b // 2, 1) if keep == "half" else b)
                    states = np.stack([a[0] for a in acts[-b:]])[rows]
                    actions = np.stack([a[1] for a in acts[-b:]])[rows]
                    q, opt, g0 = ref_ppo.update(
                        q, opt, jnp.asarray(states), jnp.asarray(actions),
                        jnp.asarray(lps[-b:], jnp.float32)[rows],
                        jnp.asarray(ev[1])[rows], name=name,
                        n_sizes=self.n_sizes, dtype=dtype)
                    params.append(jax.device_get(q))
                    grads.append(compare.norms(jax.device_get(g0)))
            out[name] = {"lps": lps, "params": params, "grads": grads}
        return out

    def check(self) -> Dict:
        """The numbers `correct` compares: the logged acts and updates
        against their replay."""
        return self.numbers(self.program_outputs(), self.reference())

    def numbers(self, outputs: Dict, ref: Dict) -> Dict:
        lp_gap, upd_gap = 0.0, 0.0
        for name in ref:
            o, r = outputs[name], ref[name]
            if len(o["lps"]) != len(r["lps"]) or \
                    len(o["params"]) != len(r["params"]):
                return {"act_logprob_gap": None, "update_change_gap": None}
            lp_gap = max([lp_gap] + [abs(a - b) for a, b in
                                     zip(o["lps"], r["lps"])])
            upd_gap = max([upd_gap] + [
                compare.change_gap(po, pr, self.p0[name], g)
                for po, pr, g in zip(o["params"], r["params"], r["grads"])])
        return {"act_logprob_gap": lp_gap, "update_change_gap": upd_gap}

