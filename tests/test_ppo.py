"""PPO agent unit tests: math + learning on toy environments."""
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ppo import (UPDATE_METRICS, PPOAgent, PPOConfig,
                            discounted_returns)


def test_discounted_returns():
    r = jnp.asarray([1.0, 0.0, 2.0])
    g = discounted_returns(r, 0.5)
    np.testing.assert_allclose(np.asarray(g), [1 + 0.5 * (0 + 0.5 * 2),
                                               0 + 0.5 * 2, 2.0])


def test_categorical_multihead_act_shapes():
    cfg = PPOConfig(state_dim=6, kind="categorical_multihead", n_categories=3)
    agent = PPOAgent(cfg, jax.random.PRNGKey(0))
    a, lp = agent.act(jax.random.PRNGKey(1), np.ones(6, np.float32))
    assert a.shape == (6,) and set(np.unique(a)) <= {0, 1, 2}
    assert np.isfinite(lp)


def test_gaussian_simplex_act():
    cfg = PPOConfig(state_dim=4, kind="gaussian_simplex")
    agent = PPOAgent(cfg, jax.random.PRNGKey(0))
    a, lp = agent.act(jax.random.PRNGKey(1), np.ones(4, np.float32))
    assert a.shape == (4,) and np.isfinite(lp)
    det, _ = agent.act(jax.random.PRNGKey(2), np.ones(4, np.float32),
                       deterministic=True)
    det2, _ = agent.act(jax.random.PRNGKey(3), np.ones(4, np.float32),
                        deterministic=True)
    np.testing.assert_allclose(det, det2)


def test_buffer_update_cycle():
    cfg = PPOConfig(state_dim=3, kind="categorical_multihead", n_categories=2,
                    buffer_size=4)
    agent = PPOAgent(cfg, jax.random.PRNGKey(0))
    for i in range(3):
        s = np.random.rand(3).astype(np.float32)
        a, lp = agent.act(jax.random.PRNGKey(i), s)
        agent.store(s, a, lp, float(i))
        assert agent.maybe_update() is None
    s = np.random.rand(3).astype(np.float32)
    a, lp = agent.act(jax.random.PRNGKey(9), s)
    agent.store(s, a, lp, 1.0)
    metrics = agent.maybe_update()
    assert metrics is not None and np.isfinite(metrics["loss"])
    assert agent.buffer == []


def test_categorical_learns_state_dependent_bandit():
    """Reward = +1 iff action matches a state-derived target; PPO must beat
    random (0.5) decisively."""
    k = 4
    cfg = PPOConfig(state_dim=k, kind="categorical_multihead", n_categories=2,
                    lr=1e-3, buffer_size=16, update_epochs=16, gamma=0.0,
                    entropy_coef=0.001)
    agent = PPOAgent(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(42)
    hits = []
    for t in range(800):
        s = rng.uniform(0.5, 2.0, size=k).astype(np.float32)
        target = (s > 1.25).astype(int)
        key, sub = jax.random.split(key)
        a, lp = agent.act(sub, s)
        reward = float(np.mean(a == target))
        hits.append(reward)
        agent.store(s, a, lp, reward)
        agent.maybe_update()
    assert np.mean(hits[-150:]) > 0.75, np.mean(hits[-150:])


def test_gaussian_improves_alignment_reward():
    """Reward favors action aligned with -state; PPO should increase it."""
    k = 4
    cfg = PPOConfig(state_dim=k, kind="gaussian_simplex", lr=1e-3,
                    buffer_size=8, update_epochs=8)
    agent = PPOAgent(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    key = jax.random.PRNGKey(7)
    rewards = []
    for t in range(400):
        s = rng.uniform(0.5, 2.0, size=k).astype(np.float32)
        key, sub = jax.random.split(key)
        a, lp = agent.act(sub, s)
        reward = -float(np.mean((np.asarray(a) + s) ** 2))
        rewards.append(reward)
        agent.store(s, a, lp, reward)
        agent.maybe_update()
    assert np.mean(rewards[-80:]) > np.mean(rewards[:80])


# --------------------------------------------------------------------- #
# parity: the agents' outputs, pinned bitwise
# --------------------------------------------------------------------- #
PARITY = Path(__file__).parent / "data" / "ppo_parity.json"


def _parity_run():
    """12 latency-only rounds on a small server: each agent's buffer fills
    twice. Returns what each round decided and rewarded, each agent's last
    update metrics, and a hash of each agent's parameter bytes."""
    from repro.fl import FLEnvironment, FLSimConfig, HAPFLServer
    cfg = FLSimConfig(dataset="mnist", n_train=300, n_test=80, n_clients=8,
                      k_per_round=4, batches_per_epoch=1, default_epochs=2,
                      batch_size=16)
    srv = HAPFLServer(FLEnvironment(cfg), seed=3)
    srv.pretrain_rl(12)
    out = {"records": [{"sizes": r.sizes, "intensities": r.intensities,
                        "reward_ppo1": r.reward_ppo1,
                        "reward_ppo2": r.reward_ppo2} for r in srv.history],
           "agents": {}}
    for agent in (srv.allocator.agent, srv.intensity.agent):
        h = hashlib.sha256()
        for leaf in jax.tree_util.tree_leaves(agent.params):
            h.update(np.asarray(leaf).tobytes())
        out["agents"][agent.name] = {"n_updates": agent.n_updates,
                                     "last_update": agent.last_update,
                                     "params_sha256": h.hexdigest()}
    return out


def test_agents_reproduce_the_pinned_rounds_bitwise():
    """Same programs on the same inputs: every decision, reward, update
    metric and parameter byte matches the pinned run."""
    want = json.loads(PARITY.read_text())
    got = json.loads(json.dumps(_parity_run()))
    assert got["records"] == want["records"]
    for name, agent in want["agents"].items():
        assert got["agents"][name]["n_updates"] == agent["n_updates"] == 2
        # the dict's key order too
        assert list(got["agents"][name]["last_update"].items()) == \
            list(agent["last_update"].items())
        assert got["agents"][name]["params_sha256"] == agent["params_sha256"]


def test_update_metrics_come_back_in_their_declared_order():
    cfg = PPOConfig(state_dim=3, kind="gaussian_simplex", buffer_size=2)
    agent = PPOAgent(cfg, jax.random.PRNGKey(0))
    for i in range(2):
        s = np.full(3, i, np.float32)
        a, lp = agent.act(jax.random.PRNGKey(i), s)
        agent.store(s, a, lp, float(i))
    metrics = agent.maybe_update()
    assert tuple(metrics) == UPDATE_METRICS
    assert metrics is agent.last_update
    assert all(type(v) is float for v in metrics.values())
    assert metrics["value_loss"] == metrics["critic_loss"]


if __name__ == "__main__":
    # writes the fixture; run from the repo root with PYTHONPATH=src
    PARITY.write_text(json.dumps(_parity_run(), indent=1) + "\n")
