"""Batched multi-client engine: parity with the sequential path + masking."""
import jax
import numpy as np
import pytest

from repro.data.pipeline import BatchLoader
from repro.fl import (BatchedClientEngine, FLEnvironment, FLSimConfig,
                      HAPFLServer)

CFG = FLSimConfig(dataset="mnist", n_train=400, n_test=100,
                  batches_per_epoch=1, default_epochs=2,
                  n_clients=6, k_per_round=4,
                  size_names=("small", "large"))


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _assert_trees_close(a, b, atol=1e-5, rtol=1e-4):
    for la, lb in zip(_leaves(a), _leaves(b)):
        np.testing.assert_allclose(la, lb, atol=atol, rtol=rtol)


def test_sample_many_matches_sample_stream():
    """The prefetch path must consume the loader rng exactly like repeated
    sample() calls — this is what makes engine parity exact."""
    x = np.arange(200 * 4, dtype=np.float32).reshape(200, 4)
    y = np.arange(200, dtype=np.int32)
    a = BatchLoader(x, y, batch_size=16, seed=11)
    b = BatchLoader(x, y, batch_size=16, seed=11)
    xs, ys = a.sample_many(7)
    for i in range(7):
        xb, yb = b.sample()
        np.testing.assert_array_equal(xs[i], xb)
        np.testing.assert_array_equal(ys[i], yb)


def test_parity_two_size_four_client_round():
    """Batched engine == sequential engine on a 2-size, 4-client cohort with
    ragged intensities, to ~1e-5 on every parameter and exactly on accuracy."""
    env_a, env_b = FLEnvironment(CFG), FLEnvironment(CFG)
    a = HAPFLServer(env_a, seed=5, engine="sequential")
    b = HAPFLServer(env_b, seed=5, engine="batched")
    clients = [0, 1, 2, 3]
    sizes = ["small", "small", "large", "large"]
    intensities = [1, 3, 2, 1]
    seq = [a._client_train(c, s, t)
           for c, s, t in zip(clients, sizes, intensities)]
    bat = b.batched_engine.train_cohort(clients, sizes, intensities,
                                        b.global_by_size, b.lite_params)
    for c, s, p_seq, p_bat in zip(clients, sizes, seq, bat):
        _assert_trees_close(p_seq, p_bat)
        # params agree to ~1e-5, so a test sample whose top-2 logits sit
        # inside that gap may flip argmax — allow one sample of slack
        for cfg_m, key in ((env_a.pool[s], "local"), (env_a.lite_cfg, "lite")):
            a = env_a.client_test_accuracy(p_seq[key], cfg_m, c)
            b = env_b.client_test_accuracy(p_bat[key], cfg_m, c)
            assert abs(a - b) <= 1.5 / min(len(env_a.partitions[c]), 256)


def test_ragged_masking_pad_invariance():
    """Power-of-two step padding must be a pure no-op: masked steps may be
    computed but can never touch parameters."""
    env_a, env_b = FLEnvironment(CFG), FLEnvironment(CFG)
    eng_a, eng_b = BatchedClientEngine(env_a), BatchedClientEngine(env_b)
    srv = HAPFLServer(env_a, seed=0)   # only for shared initial globals
    clients, sizes, intensities = [1, 4], ["small", "small"], [1, 3]
    padded = eng_a.train_cohort(clients, sizes, intensities,
                                srv.global_by_size, srv.lite_params,
                                pad_pow2=True)
    exact = eng_b.train_cohort(clients, sizes, intensities,
                               srv.global_by_size, srv.lite_params,
                               pad_pow2=False)
    for p, e in zip(padded, exact):
        _assert_trees_close(p, e, atol=0, rtol=0)


def test_full_round_server_parity():
    """End-to-end run_round parity: allocation, training, aggregation."""
    a = HAPFLServer(FLEnvironment(CFG), seed=3, engine="sequential")
    b = HAPFLServer(FLEnvironment(CFG), seed=3, engine="batched")
    rec_a, rec_b = a.run_round(), b.run_round()
    assert rec_a.sizes == rec_b.sizes
    assert rec_a.intensities == rec_b.intensities
    for c in rec_a.clients:
        assert rec_a.client_acc[c]["size"] == rec_b.client_acc[c]["size"]
        for key in ("local", "lite"):
            # ~1e-5 param agreement -> allow one argmax flip per eval set
            assert (abs(rec_a.client_acc[c][key] - rec_b.client_acc[c][key])
                    <= 1.5 / min(len(a.env.partitions[c]), 256))
    _assert_trees_close(a.lite_params, b.lite_params)
    for s in a.global_by_size:
        _assert_trees_close(a.global_by_size[s], b.global_by_size[s])


def test_unknown_engine_rejected():
    with pytest.raises(ValueError):
        HAPFLServer(FLEnvironment(CFG), engine="warp-drive")


@pytest.fixture(scope="module")
def trainers():
    """One engine's compiled group trainers, shared by the pipeline tests'
    engines so that each cohort shape compiles once."""
    return BatchedClientEngine(FLEnvironment(CFG))._trainers


def _engine(trainers):
    env = FLEnvironment(CFG)
    eng = BatchedClientEngine(env)
    eng._trainers = trainers
    return env, eng


def _groups(env, clients, sizes, intensities):
    """The cohort's size groups in dispatch order, as input positions."""
    bpe = env.cfg.batches_per_epoch
    groups = {}
    for i, (c, s) in enumerate(zip(clients, sizes)):
        key = (s, env.loaders[c].batch_size,
               1 << max(intensities[i] * bpe - 1, 0).bit_length())
        groups.setdefault(key, []).append(i)
    return list(groups.values())


def test_pipelined_cohort_equals_one_call_per_group(trainers):
    """Overlapping a group's dispatch with the previous group's collection
    changes when the host waits, never what is computed: the cohort in one
    call gives the same arrays as one call per group on a twin, and leaves
    every loader's rng where the twin's is."""
    (env_a, eng_a), (env_b, eng_b) = _engine(trainers), _engine(trainers)
    srv = HAPFLServer(env_a, seed=0)   # only for shared initial globals
    clients = [0, 1, 2, 3, 4]
    sizes = ["small", "small", "large", "large", "small"]
    intensities = [1, 2, 1, 1, 2]
    groups = _groups(env_a, clients, sizes, intensities)
    assert len(groups) >= 3
    whole = eng_a.train_cohort(clients, sizes, intensities,
                               srv.global_by_size, srv.lite_params)
    for idx in groups:
        part = eng_b.train_cohort(
            [clients[i] for i in idx], [sizes[i] for i in idx],
            [intensities[i] for i in idx], srv.global_by_size,
            srv.lite_params)
        for i, p in zip(idx, part):
            for la, lb in zip(_leaves(whole[i]), _leaves(p)):
                assert isinstance(la, np.ndarray)
                assert np.array_equal(la, lb)
    for la, lb in zip(env_a.loaders, env_b.loaders):
        assert la.rng.bit_generator.state == lb.rng.bit_generator.state


@pytest.mark.parametrize("n_groups", [1, 2, 4])
def test_two_groups_in_flight_at_most(trainers, n_groups):
    """Each group is counted; every group but the first is dispatched while
    its predecessor is still uncollected, and never a third."""
    from repro.obs import trace as obs_trace
    env, eng = _engine(trainers)
    srv = HAPFLServer(env, seed=0)
    # (size, intensity) per client; clients 0, 1, 2, 4 share a batch size
    plan = [("small", 1), ("small", 2), ("large", 1), ("large", 2)]
    clients = [0, 1, 2, 4][:n_groups]
    sizes, intensities = (list(v) for v in zip(*plan[:n_groups]))
    assert len(_groups(env, clients, sizes, intensities)) == n_groups
    dispatch, uncollected = eng._dispatch, []

    def spy(*args):
        collected = sum(ev["name"] == "cohort.collect" for ev in tr.events)
        uncollected.append(len(uncollected) + 1 - collected)
        return dispatch(*args)

    eng._dispatch = spy
    prev = obs_trace.current()
    tr = obs_trace.enable(obs_trace.Tracer())
    try:
        out = eng.train_cohort(clients, sizes, intensities,
                               srv.global_by_size, srv.lite_params)
    finally:
        obs_trace.enable(prev)           # whichever tracer was installed
    assert len(out) == n_groups and all(p is not None for p in out)
    assert tr.counts["cohort.groups"] == n_groups
    assert tr.counts.get("cohort.overlapped", 0) == n_groups - 1
    assert max(uncollected) == min(n_groups, 2)
    collects = [ev for ev in tr.events if ev["name"] == "cohort.collect"]
    assert len(collects) == n_groups
    # the group's annotation covers its dispatch, not the wait for it
    assert all(ev["parent"] is None for ev in collects)
