"""Per-wave PPO diagnostics for both HAPFL agents (DESIGN.md §16).

The PPO agents only *update* every `buffer_size` waves (paper B = 5), so
per-wave diagnostics mix two sources:

  every wave       policy entropy at the wave's acted state (one jitted
                   forward through the actor — no rng, so collecting it
                   never perturbs the simulation), the wave's reward, and
                   the buffer fill level;
  every update     the optimizer-side metrics `_ppo_update` computes
                   anyway: approx-KL vs the behaviour policy, clip
                   fraction, pre-normalization advantage mean/std, value
                   loss — carried forward on `PPOAgent.last_update` until
                   the next update replaces them.

`wave_diagnostics(server)` packages both agents' views; the server emits
it as trace counters and stamps it on the round record (`rl_diag`) — only
when `HAPFLServer.collect_rl_diag` is set (a health-tracking caller sets
it). Tracing alone does not collect them: the entropy forwards would make
a traced round do work an untraced one does not. Collecting them never
changes the simulation's outputs (pinned in tests/test_obs.py).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ppo import PPOAgent, _policy_dist
from repro.obs.trace import current as _tracer

#: last_update keys surfaced per wave (the full dict also carries
#: loss/actor_loss/critic_loss/mean_ratio/mean_return)
UPDATE_KEYS = ("approx_kl", "clip_fraction", "adv_mean", "adv_std",
               "value_loss")


def _entropy_fn(agent: PPOAgent):
    """Jitted state -> policy entropy for this agent's head, cached on the
    agent (one compile per agent, reused every wave); it compiles as
    jit_ppo_entropy."""
    fn = getattr(agent, "_obs_entropy_fn", None)
    if fn is None:
        cfg = agent.cfg

        def ppo_entropy(params, state):
            dist = _policy_dist(params, state, cfg)
            if cfg.kind == "categorical_multihead":
                logp = dist["logits"]                  # (k, delta) log-probs
                return -jnp.sum(jnp.exp(logp) * logp)
            # diagonal Gaussian: state-independent, sum over dims
            return jnp.sum(dist["log_std"]
                           + 0.5 * jnp.log(2 * jnp.pi * jnp.e))

        fn = jax.jit(ppo_entropy)
        agent._obs_entropy_fn = fn
    return fn


def policy_entropy(agent: PPOAgent, state) -> float:
    """Entropy of the agent's current policy at `state` (nats; summed over
    the per-client heads for PPO1, over action dims for PPO2)."""
    tr = _tracer()
    out = float(_entropy_fn(agent)(agent.params,
                                   jnp.asarray(np.asarray(state))))
    tr.count("ppo.launches")
    tr.count("ppo.syncs")
    return out


def agent_diagnostics(owner) -> Dict[str, Optional[float]]:
    """One agent-owner's (ModelAllocator / IntensityAllocator) per-wave
    view; `_pending` holds the state the agent just acted on."""
    agent = owner.agent
    pend = getattr(owner, "_pending", None) or {}
    d: Dict[str, Optional[float]] = {
        "reward": (float(agent.reward_history[-1])
                   if agent.reward_history else None),
        "buffer_fill": float(len(agent.buffer)),
        "n_updates": float(agent.n_updates),
        "entropy": (policy_entropy(agent, pend["state"])
                    if "state" in pend else None),
    }
    last = agent.last_update
    for k in UPDATE_KEYS:
        d[k] = (round(float(last[k]), 6) if last else None)
    return d


def wave_diagnostics(server) -> Dict[str, Dict]:
    """Both agents' diagnostics for the wave whose feedback just ran."""
    out: Dict[str, Dict] = {}
    if server.use_ppo1:
        out["ppo1"] = agent_diagnostics(server.allocator)
    if server.use_ppo2:
        out["ppo2"] = agent_diagnostics(server.intensity)
    return out
