"""The plain references in bench/references tied to the program at a small
size on the CPU: CNN forward, the mutual-KD SGD-momentum step, entropy and
the Eq. 38/39 fold, and the PPO agents (initial weights, log-probability
of an action, one buffered update)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.references import cnn as ref_cnn
from bench.references import ppo as ref_ppo

CONFIG = {"in_shape": [28, 28, 1], "n_classes": 10,
          "pool": {"lite": {"channels": [8], "hidden": 32},
                   "small": {"channels": [16, 32], "hidden": 64},
                   "large": {"channels": [32, 64, 128], "hidden": 128}}}


def _close(a, b, tol):
    leaves = jax.tree_util.tree_leaves
    for x, y in zip(leaves(a), leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=tol,
                                   atol=tol)


@pytest.mark.parametrize("size", ["lite", "small", "large"])
def test_forward_matches_apply_cnn(size):
    from repro.models.cnn import apply_cnn, cnn_pool
    cfg = cnn_pool("mnist")[size]
    p = ref_cnn.init_params(jax.random.PRNGKey(3), CONFIG, size)
    x = jax.random.normal(jax.random.PRNGKey(4), (5, 28, 28, 1))
    _close(ref_cnn.forward(p, x), apply_cnn(p, cfg, x), 1e-4)


def test_init_layout_matches_init_cnn():
    from repro.models.cnn import cnn_pool, init_cnn
    key = jax.random.PRNGKey(7)
    for size in ("lite", "small", "large"):
        mine = ref_cnn.init_params(key, CONFIG, size)
        theirs = init_cnn(key, cnn_pool("mnist")[size])
        assert jax.tree_util.tree_structure(mine) == \
            jax.tree_util.tree_structure(theirs)
        _close(mine, theirs, 1e-6)


def test_client_training_matches_the_mutual_kd_step():
    from repro.core.distill import make_mutual_train_fns
    from repro.models.cnn import apply_cnn, cnn_pool
    pool = cnn_pool("mnist")
    step, init_opt = make_mutual_train_fns(
        functools.partial(lambda p, x, c: apply_cnn(p, c, x), c=pool["small"]),
        functools.partial(lambda p, x, c: apply_cnn(p, c, x), c=pool["lite"]),
        lr=5e-3)
    start = {"local": ref_cnn.init_params(jax.random.PRNGKey(0), CONFIG,
                                          "small"),
             "lite": ref_cnn.init_params(jax.random.PRNGKey(1), CONFIG,
                                         "lite")}
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(3, 8, 28, 28, 1)).astype(np.float32)
    ys = rng.integers(0, 10, size=(3, 8)).astype(np.int32)
    p, o = start, init_opt(start)
    for x, y in zip(xs, ys):
        p, o, _ = step(p, o, x, y)
    mine, _ = ref_cnn.train_client(start, xs, ys,
                                   {"lr": 5e-3, "momentum": 0.9})
    _close(mine, p, 1e-5)


def test_entropy_and_fold_match_the_server_aggregation():
    from repro.core.aggregation import (aggregation_weights, group_aggregate,
                                        information_entropy,
                                        weighted_aggregate)
    y = np.array([0, 0, 1, 3, 3, 3, 9])
    assert ref_cnn.entropy(y, 10) == pytest.approx(
        information_entropy(np.bincount(y, minlength=10)))
    ents, accs = [1.2, 2.0, 0.7], [0.3, 0.5, 0.1]
    np.testing.assert_allclose(ref_cnn.weights(ents, accs),
                               aggregation_weights(ents, accs))
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    g = ref_cnn.init_params(keys[0], CONFIG, "small")
    cs = [ref_cnn.init_params(k, CONFIG, "small") for k in keys[1:]]
    w = ref_cnn.weights(ents, accs)
    _close(ref_cnn.fold(g, cs, w), weighted_aggregate(g, cs, w), 1e-5)
    sizes = ["small", "small", "small"]
    _, by_size = ref_cnn.aggregate(g, {"small": g}, sizes,
                                   [{"local": c, "lite": c} for c in cs],
                                   ents, accs, accs)
    _close(by_size["small"],
           group_aggregate({"small": g}, cs, sizes, ents, accs)["small"], 1e-5)


def _server(k=4):
    from repro.fl import FLEnvironment, FLSimConfig, HAPFLServer
    cfg = FLSimConfig(dataset="mnist", n_clients=6, k_per_round=k,
                      n_train=200, n_test=50, default_epochs=2, batch_size=8)
    return HAPFLServer(FLEnvironment(cfg), seed=11)


def test_ppo_agents_match_the_server_agents():
    srv = _server()
    mine = ref_ppo.init_agents(jax.random.PRNGKey(11), 4, 2)
    agents = {"ppo1": srv.allocator.agent, "ppo2": srv.intensity.agent}
    for name, agent in agents.items():
        _close(mine[name], agent.params, 1e-6)
        kind = ref_ppo.AGENTS[name]["kind"]
        state = np.linspace(0.0, 1.5, 4).astype(np.float32)
        action, lp = agent.act(jax.random.PRNGKey(5), state)
        got = ref_ppo.logprob(mine[name], jnp.asarray(state),
                              jnp.asarray(action), kind=kind, n_sizes=2,
                              dtype=jnp.float32)
        assert float(got) == pytest.approx(lp, abs=1e-4)


def test_ppo_update_matches_the_agent_update():
    srv = _server()
    agent = srv.allocator.agent
    mine = ref_ppo.init_agents(jax.random.PRNGKey(11), 4, 2)["ppo1"]
    rng = np.random.default_rng(1)
    states, actions, lps = [], [], []
    for i in range(agent.cfg.buffer_size):
        s = rng.uniform(0, 2, 4).astype(np.float32)
        a, lp = agent.act(jax.random.PRNGKey(i), s)
        agent.store(s, a, lp, float(rng.normal()))
        states.append(s)
        actions.append(a)
        lps.append(lp)
    rewards = np.asarray(agent.reward_history, np.float32)
    agent.maybe_update()
    got, _, _ = ref_ppo.update(
        mine, ref_ppo.init_opt(mine), jnp.asarray(np.stack(states)),
        jnp.asarray(np.stack(actions)), jnp.asarray(lps, jnp.float32),
        jnp.asarray(rewards), name="ppo1", n_sizes=2, dtype=jnp.float32)
    _close(got, agent.params, 1e-5)
