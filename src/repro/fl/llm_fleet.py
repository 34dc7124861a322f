"""HAPFL over a fleet of TRANSFORMER clients — the paper's technique driving
the assigned architectures end-to-end (smoke scale on CPU; the same step
lowers at full scale in the dry-run).

Each client trains a size-variant of one assigned arch family together with
the shared LiteModel via mutual KD (Eqs. 33-35); PPO1 picks the variant,
PPO2 the number of local steps; aggregation is entropy+accuracy weighted
per size group (Eqs. 36-39). Non-IID-ness comes from per-client Zipf token
distributions.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.aggregation import (aggregation_weights, group_aggregate,
                                    information_entropy, weighted_aggregate)
from repro.core.allocation import ModelAllocator
from repro.core.intensity import IntensityAllocator
from repro.core.latency import LatencyModel, straggling_latency
from repro.core.population import ClientStore
from repro.models.api import init_model
from repro.models.transformer import apply_model
from repro.train.step import (TrainStepConfig, make_hapfl_train_step,
                              make_train_state)


@dataclass
class FleetConfig:
    arch: str = "llama3.2-3b"
    n_clients: int = 6
    k_per_round: int = 4
    max_speed_ratio: float = 8.0
    seq: int = 64
    batch: int = 4
    default_steps: int = 4       # per-round local steps baseline
    lr: float = 1e-2
    seed: int = 0


class LLMFleet:
    def __init__(self, cfg: FleetConfig):
        self.cfg = cfg
        base = get_config(cfg.arch).smoke()
        small = dataclasses.replace(base, name=f"{base.name}-s", n_layers=1,
                                    d_ff=max(base.d_ff // 2, 128) if base.d_ff
                                    else 0)
        self.pool = {"small": small, "large": base}
        self.lite = dataclasses.replace(base.lite(), dtype=jnp.float32,
                                        remat=False, scan_layers=False,
                                        vocab_size=base.vocab_size)
        key = jax.random.PRNGKey(cfg.seed)
        ks = jax.random.split(key, 8)
        tcfg = TrainStepConfig(lr=cfg.lr)
        self.tcfg = tcfg
        # global params per size + shared lite (lite params tracked separately)
        self.state_template = {
            s: make_train_state(jax.random.fold_in(ks[0], i), c, self.lite,
                                tcfg)
            for i, (s, c) in enumerate(self.pool.items())}
        self.global_by_size = {s: self.state_template[s]["params"]["local"]
                               for s in self.pool}
        self.lite_params = self.state_template["small"]["params"]["lite"]
        self._steps = {s: jax.jit(make_hapfl_train_step(c, self.lite, tcfg))
                       for s, c in self.pool.items()}
        # client data: per-client Zipf token streams (non-IID exponents)
        rng = np.random.default_rng(cfg.seed)
        V = base.vocab_size
        self.client_tokens = []
        self.entropies = []
        for i in range(cfg.n_clients):
            a = rng.uniform(1.0, 1.8)
            p = 1.0 / np.arange(1, V + 1) ** a
            p /= p.sum()
            toks = rng.choice(V, size=20_000, p=p).astype(np.int32)
            self.client_tokens.append(toks)
            hist = np.bincount(toks, minlength=V)
            self.entropies.append(information_entropy(hist))
        self.store = ClientStore.for_partition(
            cfg.n_clients, cfg.max_speed_ratio,
            [len(t) for t in self.client_tokens], self.entropies,
            seed=cfg.seed, size_names=tuple(self.pool))
        self.latency = LatencyModel(
            {s: float(c.num_params()) for s, c in self.pool.items()},
            float(self.lite.num_params()), cost_scale=1e-9, seed=cfg.seed)
        self.allocator = ModelAllocator(cfg.k_per_round, list(self.pool),
                                        ks[1])
        self.intensity = IntensityAllocator(
            cfg.k_per_round, ks[2],
            total_intensity=cfg.default_steps * cfg.k_per_round)
        self.key = ks[3]
        self.rng = np.random.default_rng(cfg.seed + 1)
        self._round = 0
        self.history: List[Dict] = []

    # ------------------------------------------------------------------ #
    def _batch(self, client: int):
        toks = self.client_tokens[client]
        cfg = self.cfg
        i = self.rng.integers(0, len(toks) - cfg.batch * (cfg.seq + 1) - 1)
        chunk = toks[i:i + cfg.batch * (cfg.seq + 1)].reshape(
            cfg.batch, cfg.seq + 1)
        return {"tokens": jnp.asarray(chunk[:, :-1]),
                "labels": jnp.asarray(chunk[:, 1:])}

    def _next_token_acc(self, params, model_cfg, client: int) -> float:
        b = self._batch(client)
        logits, _, _ = apply_model(params, model_cfg, b)
        pred = jnp.argmax(logits, -1)
        return float(jnp.mean(pred == b["labels"]))

    def run_round(self) -> Dict:
        cfg = self.cfg
        r = self._round
        clients = sorted(self.rng.choice(cfg.n_clients, cfg.k_per_round,
                                         replace=False).tolist())
        assess = [float(a) for a in self.latency.assessment_times(
            self.store, clients, r)]
        self.key, k1, k2 = jax.random.split(self.key, 3)
        sizes, _ = self.allocator.allocate(k1, assess)
        modified = [self.latency.relative_time_ratio(s) * t / min(assess)
                    for s, t in zip(sizes, assess)]
        taus, _ = self.intensity.assign(k2, modified)

        local_times = [float(t) for t in self.latency.local_train_times(
            self.store, clients, r, sizes, taus)]
        params_out, accs_local, accs_lite = [], [], []
        for c, s, tau in zip(clients, sizes, taus):
            state = {"params": {"local": self.global_by_size[s],
                                "lite": self.lite_params},
                     "opt": self.state_template[s]["opt"]}
            step = self._steps[s]
            for _ in range(int(tau)):
                state, metrics = step(state, self._batch(c))
            params_out.append(state["params"])
            accs_local.append(self._next_token_acc(state["params"]["local"],
                                                   self.pool[s], c))
            accs_lite.append(self._next_token_acc(state["params"]["lite"],
                                                  self.lite, c))
        ents = [self.entropies[c] for c in clients]
        self.lite_params = weighted_aggregate(
            self.lite_params, [p["lite"] for p in params_out],
            aggregation_weights(ents, accs_lite))
        self.global_by_size = group_aggregate(
            self.global_by_size, [p["local"] for p in params_out], sizes,
            ents, accs_local)
        self.allocator.feedback(local_times, taus)
        self.intensity.feedback(local_times)
        rec = {"round": r, "clients": clients, "sizes": sizes, "taus": taus,
               "straggling": straggling_latency(local_times),
               "acc_local_mean": float(np.mean(accs_local)),
               "acc_lite_mean": float(np.mean(accs_lite))}
        self.history.append(rec)
        self._round += 1
        return rec
