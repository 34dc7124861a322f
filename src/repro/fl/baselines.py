"""Baselines: FedAvg, FedProx, pFedMe, FedDdrl (paper §V.B) + ablations.

All share the latency/simulation substrate so straggling-latency and
training-time comparisons are apples-to-apples with HAPFL:
  FedAvg  — one global model (uniform arch), uniform intensity, param mean.
  FedProx — FedAvg + proximal term (mu) in the client loss.
  pFedMe  — personalized: client keeps a personal model trained with a
            Moreau-envelope-style proximal pull to the global model.
  FedDdrl — DRL (our PPO2) adjusts per-client local epochs + early
            termination of the slowest client's surplus epochs; fixed arch.
Ablations (paper Fig. 25): HAPFL with fixed size / fixed intensity are run
via HAPFLServer(use_ppo1=False) / (use_ppo2=False).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import fedavg_aggregate
from repro.core.distill import make_single_train_fns
from repro.core.intensity import IntensityAllocator
from repro.core.latency import straggling_latency
from repro.data.pipeline import prefetch_client
from repro.fl.batched import next_pow2, scan_train
from repro.fl.env import FLEnvironment
from repro.models.cnn import apply_cnn, init_cnn


@dataclass
class BaselineRecord:
    round_idx: int
    straggling: float
    wall_time: float
    acc_global: float
    client_acc: Dict[int, float]
    latency_only: bool = False


class BaselineRunner:
    """algo in {"fedavg", "fedprox", "pfedme", "fedddrl"}."""

    def __init__(self, env: FLEnvironment, algo: str, seed: int = 0,
                 size: str = None, prox_mu: float = 0.1):
        self.env, self.algo = env, algo
        cfg = env.cfg
        self.size = size or list(env.pool)[0]
        self.cnn_cfg = env.pool[self.size]
        mu = {"fedprox": prox_mu, "pfedme": 15.0 * cfg.lr}.get(algo, 0.0)
        raw_step, init_opt = make_single_train_fns(
            functools.partial(lambda p, x, cc: apply_cnn(p, cc, x),
                              cc=self.cnn_cfg),
            lr=cfg.lr, prox_mu=mu)
        # one scan dispatch per client instead of one per batch
        self._scan_train = scan_train(raw_step, init_opt)
        key = jax.random.PRNGKey(seed)
        self.global_params = init_cnn(key, self.cnn_cfg)
        self.personal = {i: self.global_params
                         for i in range(cfg.n_clients)} if algo == "pfedme" else None
        self.intensity = (IntensityAllocator(
            cfg.k_per_round, jax.random.fold_in(key, 1),
            total_intensity=cfg.default_epochs * cfg.k_per_round)
            if algo == "fedddrl" else None)
        self.key = jax.random.fold_in(key, 2)
        self.history: List[BaselineRecord] = []
        self._round = 0

    def _train_client(self, client: int, epochs: int, start_params):
        env = self.env
        n_steps = epochs * env.cfg.batches_per_epoch
        # pow2 padding + masking keeps fedddrl's varying intensities from
        # forcing a recompile per distinct step count; the other baselines
        # train a constant epoch count, so padding would only waste compute
        pad = next_pow2(n_steps) if self.algo == "fedddrl" else n_steps
        xs, ys, mask = prefetch_client(env.loaders[client], n_steps,
                                       pad_to=pad)
        return self._scan_train(start_params, jnp.asarray(xs),
                                jnp.asarray(ys), jnp.asarray(mask),
                                self.global_params)

    def run_round(self, latency_only: bool = False) -> BaselineRecord:
        """One baseline round. latency_only skips CNN training, evaluation
        and aggregation (straggling/wall-time benchmarking — the latency
        figures only need the scheduling decisions, not the models)."""
        env, cfg = self.env, self.env.cfg
        r = self._round
        clients = env.select_clients()
        assess = [float(a) for a in env.latency.assessment_times(
            env.store, clients, r)]
        size_names = [self.size] * len(clients)
        if self.algo == "fedddrl":
            self.key, k = jax.random.split(self.key)
            intensities, _ = self.intensity.assign(
                k, (np.asarray(assess) / min(assess)).tolist())
            # early client termination: cap the slowest client's epochs
            t_pred = env.latency.local_train_times(
                env.store, clients, r, size_names, intensities,
                include_lite=False)
            worst = int(np.argmax(t_pred))
            intensities[worst] = max(1, intensities[worst] // 2)
        else:
            intensities = [cfg.default_epochs] * len(clients)

        local_times = [float(t) for t in env.latency.local_train_times(
            env.store, clients, r, size_names, intensities,
            include_lite=False)]
        client_params, client_acc = [], {}
        if not latency_only:
            for c, e in zip(clients, intensities):
                start = (self.personal[c] if self.algo == "pfedme"
                         else self.global_params)
                p = self._train_client(c, e, start)
                client_params.append(p)
                if self.algo == "pfedme":
                    self.personal[c] = p
                client_acc[c] = env.client_test_accuracy(p, self.cnn_cfg, c)
            sizes = [len(env.partitions[c]) for c in clients]
            self.global_params = fedavg_aggregate(client_params, sizes)
        if self.algo == "fedddrl":
            self.intensity.feedback(local_times)

        rec = BaselineRecord(
            round_idx=r, straggling=straggling_latency(local_times),
            wall_time=max(a + t for a, t in zip(assess, local_times)),
            acc_global=(0.0 if latency_only else
                        env.test_accuracy(self.global_params, self.cnn_cfg)),
            client_acc=client_acc, latency_only=latency_only)
        self.history.append(rec)
        self._round += 1
        return rec

    def run(self, rounds: int, verbose: bool = False) -> List[BaselineRecord]:
        for _ in range(rounds):
            rec = self.run_round()
            if verbose:
                print(f"[{self.algo}] round {rec.round_idx:3d} "
                      f"stragg={rec.straggling:8.2f} acc={rec.acc_global:.3f}")
        return self.history

    def summary(self) -> Dict[str, float]:
        # latency_only rounds train/evaluate nothing — accuracy stats must
        # come from real rounds only (mirrors HAPFLServer.summary)
        h = [r for r in self.history if not r.latency_only] or self.history
        warm = h[len(h) // 3:] or h
        out = {
            "mean_straggling": float(np.mean([r.straggling for r in warm])),
            "total_time": float(np.sum([r.wall_time for r in h])),
            "final_acc": h[-1].acc_global,
        }
        if self.algo == "pfedme":
            accs = [list(r.client_acc.values()) for r in h[-5:]]
            flat = [a for row in accs for a in row]
            if flat:
                out["personal_acc_mean"] = float(np.mean(flat))
                out["personal_acc_max"] = float(np.max(flat))
        return out
