"""PPO (actor-critic, clipped objective) in pure JAX — the paper's §IV.C.3.

Two policy heads are supported:
  * "categorical_multihead" — PPO1: one delta-way categorical per client
    (heterogeneous model allocation, Eq. 18-19).
  * "gaussian_simplex"      — PPO2: a Gaussian over k pre-softmax logits;
    the environment softmaxes the sampled action into the intensity simplex
    (Eq. 26). Log-probs are taken on the Gaussian.

Both agents keep an experience buffer of (state, action, logprob, reward)
and run the clipped-PPO update (Eqs. 29-32) once the buffer is full
(paper: B = 5), exactly like Algorithm 1 lines 25-30.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import current as _tracer
from repro.optim import adamw


# --------------------------------------------------------------------- #
# tiny MLP substrate
# --------------------------------------------------------------------- #
def _mlp_init(key, sizes, dtype=jnp.float32):
    params = []
    for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:])):
        k1, key = jax.random.split(key)
        params.append({
            "w": (jax.random.normal(k1, (a, b)) / jnp.sqrt(a)).astype(dtype),
            "b": jnp.zeros((b,), dtype),
        })
    return params


def _mlp_apply(params, x, final_act=None):
    for i, layer in enumerate(params):
        x = x @ layer["w"] + layer["b"]
        if i < len(params) - 1:
            x = jnp.tanh(x)
    return final_act(x) if final_act else x


#: the metrics `_ppo_update` returns, in the order of its metrics vector
#: and of `PPOAgent.last_update`'s keys
UPDATE_METRICS = ("actor_loss", "adv_mean", "adv_std", "approx_kl",
                  "clip_fraction", "critic_loss", "entropy", "loss",
                  "mean_ratio", "mean_return", "value_loss")


@dataclass
class PPOConfig:
    state_dim: int                    # k (clients per round)
    kind: str                         # categorical_multihead | gaussian_simplex
    n_categories: int = 3             # delta for PPO1
    hidden: Tuple[int, ...] = (64, 64)
    lr: float = 3e-4
    clip_eps: float = 0.2             # paper Table II
    gamma: float = 0.9
    update_epochs: int = 4
    entropy_coef: float = 0.01
    buffer_size: int = 5              # paper Table II (B)
    value_coef: float = 0.5
    init_log_std: float = -0.5


class PPOAgent:
    """Stateful wrapper: jit-compiled act/update, python-side buffer.

    `name` ("ppo1", "ppo2") names the agent's trace spans: `<name>.act`,
    and `<name>.update` when the buffer was full. Each holds the child
    spans `ppo.put` (host->device), `ppo.dispatch` (the jitted call) and
    `ppo.sync` (the one blocking wait on the device for the call's
    outputs). The counts `ppo.launches` and `ppo.syncs` tally programs
    called and arrays copied to the host: two per act (action and
    log-probability, copied together), one per update (the metrics
    vector) (DESIGN.md §16).
    """

    def __init__(self, cfg: PPOConfig, key, name: str = "ppo"):
        self.cfg = cfg
        self.name = name
        self._span_act, self._span_update = f"{name}.act", f"{name}.update"
        k1, k2, k3 = jax.random.split(key, 3)
        out_dim = (cfg.state_dim * cfg.n_categories
                   if cfg.kind == "categorical_multihead" else cfg.state_dim)
        self.params = {
            "actor": _mlp_init(k1, (cfg.state_dim,) + cfg.hidden + (out_dim,)),
            "critic": _mlp_init(k2, (cfg.state_dim,) + cfg.hidden + (1,)),
        }
        if cfg.kind == "gaussian_simplex":
            self.params["log_std"] = jnp.full((cfg.state_dim,), cfg.init_log_std)
        opt = adamw(cfg.lr)
        self.opt = opt
        self.opt_state = opt.init(self.params)
        self.buffer: List[Dict[str, np.ndarray]] = []
        self.reward_history: List[float] = []
        self.last_update: Optional[Dict[str, float]] = None
        self.n_updates = 0
        # named functions, so the programs compile as jit_ppo_act and
        # jit_ppo_update (a bare partial compiles as jit__unknown)
        def ppo_act(params, key, state, deterministic):
            return _act(params, key, state, deterministic, cfg=cfg)

        def ppo_update(params, opt_state, batch):
            return _ppo_update(params, opt_state, batch, cfg=cfg)

        self._act = jax.jit(ppo_act, static_argnames=("deterministic",))
        self._update = jax.jit(ppo_update)

    # ------------------------------------------------------------------ #
    def act(self, key, state: np.ndarray, deterministic: bool = False):
        tr = _tracer()
        with tr.span(self._span_act):
            with tr.span("ppo.put"):
                x = jnp.asarray(state)
            with tr.span("ppo.dispatch"):
                action, logprob = self._act(self.params, key, x,
                                            deterministic)
            with tr.span("ppo.sync"):
                action, logprob = jax.device_get((action, logprob))
                out = action, float(logprob)
            tr.count("ppo.launches")
            tr.count("ppo.syncs", 2)
        return out

    def store(self, state, action, logprob, reward):
        self.buffer.append({"state": np.asarray(state, np.float32),
                            "action": np.asarray(action),
                            "logprob": np.float32(logprob),
                            "reward": np.float32(reward)})
        self.reward_history.append(float(reward))

    def maybe_update(self) -> Optional[Dict[str, float]]:
        """Algorithm 1: update once the buffer is full, then clear it."""
        if len(self.buffer) < self.cfg.buffer_size:
            return None
        tr = _tracer()
        with tr.span(self._span_update):
            with tr.span("ppo.put"):
                batch = {k: jnp.asarray(np.stack([b[k] for b in self.buffer]))
                         for k in self.buffer[0]}
            with tr.span("ppo.dispatch"):
                self.params, self.opt_state, metrics = self._update(
                    self.params, self.opt_state, batch)
            tr.count("ppo.launches")
            self.buffer.clear()
            with tr.span("ppo.sync"):
                vec = np.asarray(metrics)
            tr.count("ppo.syncs")
            out = {k: float(v) for k, v in zip(UPDATE_METRICS, vec)}
        self.last_update = out
        self.n_updates += 1
        return out


# --------------------------------------------------------------------- #
# functional core (jit)
# --------------------------------------------------------------------- #
def _policy_dist(params, state, cfg: PPOConfig):
    out = _mlp_apply(params["actor"], state)
    if cfg.kind == "categorical_multihead":
        logits = out.reshape(state.shape[:-1] + (cfg.state_dim, cfg.n_categories))
        return {"logits": jax.nn.log_softmax(logits, -1)}
    return {"mean": out, "log_std": params["log_std"]}


def _act(params, key, state, deterministic, *, cfg: PPOConfig):
    dist = _policy_dist(params, state, cfg)
    if cfg.kind == "categorical_multihead":
        logp_all = dist["logits"]                       # (k, delta)
        if deterministic:
            action = jnp.argmax(logp_all, -1)
        else:
            action = jax.random.categorical(key, logp_all, -1)
        logprob = jnp.sum(jnp.take_along_axis(logp_all, action[..., None],
                                              -1)[..., 0])
        return action, logprob
    mean, log_std = dist["mean"], dist["log_std"]
    std = jnp.exp(log_std)
    eps = jnp.where(deterministic, 0.0,
                    jax.random.normal(key, mean.shape))
    action = mean + std * eps
    logprob = jnp.sum(-0.5 * jnp.square((action - mean) / std)
                      - log_std - 0.5 * jnp.log(2 * jnp.pi))
    return action, logprob


def _logprob_entropy(params, state, action, cfg: PPOConfig):
    dist = _policy_dist(params, state, cfg)
    if cfg.kind == "categorical_multihead":
        logp_all = dist["logits"]
        lp = jnp.sum(jnp.take_along_axis(
            logp_all, action.astype(jnp.int32)[..., None], -1)[..., 0], -1)
        ent = -jnp.sum(jnp.exp(logp_all) * logp_all, (-2, -1))
        return lp, ent
    mean, log_std = dist["mean"], dist["log_std"]
    std = jnp.exp(log_std)
    lp = jnp.sum(-0.5 * jnp.square((action - mean) / std)
                 - log_std - 0.5 * jnp.log(2 * jnp.pi), -1)
    ent = jnp.sum(log_std + 0.5 * jnp.log(2 * jnp.pi * jnp.e))
    ent = jnp.broadcast_to(ent, lp.shape)
    return lp, ent


def discounted_returns(rewards, gamma):
    """G_r = sum_t gamma^t R_{r+t} over the buffer trajectory (Eq. 29)."""
    def body(carry, r):
        g = r + gamma * carry
        return g, g
    _, rev = jax.lax.scan(body, 0.0, rewards[::-1])
    return rev[::-1]


def _ppo_update(params, opt_state, batch, *, cfg: PPOConfig):
    states = batch["state"]          # (B, k)
    actions = batch["action"]
    old_logprob = batch["logprob"]   # (B,)
    returns = discounted_returns(batch["reward"], cfg.gamma)
    # standardize returns per update: makes the agent invariant to the
    # reward scale (latency magnitudes differ per dataset/model pool)
    returns = ((returns - jnp.mean(returns))
               / (jnp.std(returns) + 1e-6))
    # A_r = G_r - V(S_r) (Eq. 31), normalized for stability
    values_old = jax.vmap(lambda s: _mlp_apply(params["critic"], s)[0])(states)
    adv_raw = returns - values_old
    adv_norm = (adv_raw - jnp.mean(adv_raw)) / (jnp.std(adv_raw) + 1e-6)

    def loss_fn(p):
        values = jax.vmap(lambda s: _mlp_apply(p["critic"], s)[0])(states)
        adv = jax.lax.stop_gradient(adv_norm)
        lp, ent = jax.vmap(
            lambda s, a: _logprob_entropy(p, s, a, cfg))(states, actions)
        ratio = jnp.exp(lp - old_logprob)                       # rho_r (Eq. 30)
        unclipped = ratio * adv
        clipped = jnp.clip(ratio, 1 - cfg.clip_eps, 1 + cfg.clip_eps) * adv
        actor_loss = -jnp.mean(jnp.minimum(unclipped, clipped))
        critic_loss = jnp.mean(jnp.square(values - returns))    # Eq. 32
        total = (actor_loss + cfg.value_coef * critic_loss
                 - cfg.entropy_coef * jnp.mean(ent))
        # observability side channel (repro.obs.rl): approx-KL vs the
        # behaviour policy, the fraction of ratios the clip bites, and the
        # policy entropy — all from tensors the loss already computes
        diag = (jnp.mean(old_logprob - lp),
                jnp.mean((jnp.abs(ratio - 1.0) > cfg.clip_eps)
                         .astype(jnp.float32)),
                jnp.mean(ent))
        return total, (actor_loss, critic_loss, jnp.mean(ratio), diag)

    opt = adamw(cfg.lr)

    def epoch(carry, _):
        p, s = carry
        (loss, (al, cl, ratio, diag)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(p)
        upd, s = opt.update(grads, s, p)
        p = jax.tree_util.tree_map(lambda a, u: a + u, p, upd)
        return (p, s), (loss, al, cl, ratio) + diag

    (params, opt_state), (losses, als, cls, ratios, kls, clips, ents) = \
        jax.lax.scan(epoch, (params, opt_state), None,
                     length=cfg.update_epochs)
    metrics = {"loss": losses[-1], "actor_loss": als[-1],
               "critic_loss": cls[-1], "mean_ratio": ratios[-1],
               "mean_return": jnp.mean(returns),
               # RL diagnostics (DESIGN.md §16): last-epoch policy drift +
               # pre-normalization advantage spread + value loss alias
               "approx_kl": kls[-1], "clip_fraction": clips[-1],
               "entropy": ents[-1], "value_loss": cls[-1],
               "adv_mean": jnp.mean(adv_raw), "adv_std": jnp.std(adv_raw)}
    # one vector, so the host reads an update's metrics in one copy
    return params, opt_state, jnp.stack([metrics[k] for k in UPDATE_METRICS])
