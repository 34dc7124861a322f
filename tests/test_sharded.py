"""Mesh-sharded cohort engine: parity with the batched engine, padding
invariants, sharded Pallas kernel wrappers, and the CNN-pool sharding
rules. In-process tests run on the single host CPU device (a (1, 1)
debug mesh — the sharded program with one shard); the subprocess test
forces 4 host devices and pins parity across mesh sizes 1/2/4."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.fl import (BatchedClientEngine, FLEnvironment, FLSimConfig,
                      HAPFLServer, ShardedClientEngine)
from repro.fl.sharded import pad_to_mesh
from repro.kernels import (ref, sharded_flash_attention, sharded_kd_loss,
                           sharded_rmsnorm)
from repro.launch.mesh import make_debug_mesh
from repro.launch.sharding import param_pspec
from repro.models.cnn import cnn_pool, init_cnn

CFG = FLSimConfig(dataset="mnist", n_train=400, n_test=100,
                  batches_per_epoch=1, default_epochs=2,
                  n_clients=6, k_per_round=4,
                  size_names=("small", "large"))


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _assert_trees_close(a, b, atol=1e-5, rtol=1e-4):
    """Same tolerance discipline as tests/test_batched.py."""
    for la, lb in zip(_leaves(a), _leaves(b)):
        np.testing.assert_allclose(la, lb, atol=atol, rtol=rtol)


# ------------------------------------------------------------------ #
# pure invariants
# ------------------------------------------------------------------ #

def test_pad_to_mesh_invariant():
    # pow2 floor of 4, then rounded up to a mesh multiple
    assert pad_to_mesh(1, 1) == 4
    assert pad_to_mesh(3, 1) == 4
    assert pad_to_mesh(5, 1) == 8
    assert pad_to_mesh(2, 4) == 4
    assert pad_to_mesh(5, 4) == 8
    assert pad_to_mesh(12, 3) == 18        # 16 -> next multiple of 3
    for n in range(1, 40):
        for shards in (1, 2, 4, 8):
            p = pad_to_mesh(n, shards)
            assert p >= n and p % shards == 0 and p >= 4


def test_make_debug_mesh_axes():
    mesh = make_debug_mesh()
    assert mesh.axis_names == ("data", "model")
    assert int(mesh.shape["data"]) == len(jax.devices())
    assert int(mesh.shape["model"]) == 1


def test_sharded_engine_rejects_missing_axis():
    mesh = jax.make_mesh((1,), ("replica",))
    with pytest.raises(ValueError):
        ShardedClientEngine(FLEnvironment(CFG), mesh=mesh)


def test_mesh_kwarg_requires_sharded_engine():
    with pytest.raises(ValueError):
        HAPFLServer(FLEnvironment(CFG), mesh=make_debug_mesh(),
                    engine="batched")


# ------------------------------------------------------------------ #
# engine parity (single-shard mesh in the tier-1 process)
# ------------------------------------------------------------------ #

def test_sharded_matches_batched_cohort():
    """Sharded engine == batched engine on a 2-size ragged cohort. Both
    vmap the identical make_train_one body, so this is exact on a
    single-shard mesh (asserted bitwise), well inside the ~1e-5
    discipline of the batched-vs-sequential tests."""
    env_a, env_b = FLEnvironment(CFG), FLEnvironment(CFG)
    a, b = BatchedClientEngine(env_a), ShardedClientEngine(env_b)
    srv = HAPFLServer(env_a, seed=0)    # only for shared initial globals
    clients = [0, 1, 2, 3]
    sizes = ["small", "small", "large", "large"]
    intensities = [1, 3, 2, 1]
    pa = a.train_cohort(clients, sizes, intensities,
                        srv.global_by_size, srv.lite_params)
    pb = b.train_cohort(clients, sizes, intensities,
                        srv.global_by_size, srv.lite_params)
    for ta, tb in zip(pa, pb):
        _assert_trees_close(ta, tb, atol=0, rtol=0)


def test_sharded_pad_invariance():
    """pow2 client/step padding through the sharded path must be a pure
    no-op, exactly like the batched engine's (test_batched.py)."""
    env_a, env_b = FLEnvironment(CFG), FLEnvironment(CFG)
    eng_a, eng_b = ShardedClientEngine(env_a), ShardedClientEngine(env_b)
    srv = HAPFLServer(env_a, seed=0)
    clients, sizes, intensities = [1, 4], ["small", "small"], [1, 3]
    padded = eng_a.train_cohort(clients, sizes, intensities,
                                srv.global_by_size, srv.lite_params,
                                pad_pow2=True)
    exact = eng_b.train_cohort(clients, sizes, intensities,
                               srv.global_by_size, srv.lite_params,
                               pad_pow2=False)
    for p, e in zip(padded, exact):
        _assert_trees_close(p, e, atol=0, rtol=0)


def test_server_round_parity_sharded_vs_batched():
    """End-to-end run_round: engine='sharded' is interchangeable with
    engine='batched' (allocation, training, aggregation)."""
    a = HAPFLServer(FLEnvironment(CFG), seed=3, engine="batched")
    b = HAPFLServer(FLEnvironment(CFG), seed=3, engine="sharded")
    rec_a, rec_b = a.run_round(), b.run_round()
    assert rec_a.sizes == rec_b.sizes
    assert rec_a.intensities == rec_b.intensities
    _assert_trees_close(a.lite_params, b.lite_params)
    for s in a.global_by_size:
        _assert_trees_close(a.global_by_size[s], b.global_by_size[s])
    assert b.mesh is b.batched_engine.mesh


def test_auto_mesh_selects_sharded_engine():
    srv = HAPFLServer(FLEnvironment(CFG), mesh=make_debug_mesh())
    assert srv.engine == "sharded"
    assert isinstance(srv.batched_engine, ShardedClientEngine)


# ------------------------------------------------------------------ #
# sharded Pallas kernel wrappers
# ------------------------------------------------------------------ #

def test_sharded_kd_loss_matches_ref():
    mesh = make_debug_mesh()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(128, 100)).astype(np.float32)
    y = rng.normal(size=(128, 100)).astype(np.float32)
    lab = rng.integers(0, 100, size=(128,)).astype(np.int32)
    got = sharded_kd_loss(x, y, lab, mesh)
    want = ref.kd_loss_ref(jnp.asarray(x), jnp.asarray(y), jnp.asarray(lab))
    for k in ("ce_x", "ce_y", "kl_xy", "kl_yx"):
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   atol=2e-5, rtol=1e-4)


def test_sharded_rmsnorm_and_flash_match_ref():
    mesh = make_debug_mesh()
    rng = np.random.default_rng(1)
    x = rng.normal(size=(32, 64)).astype(np.float32)
    s = rng.normal(size=(64,)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(sharded_rmsnorm(x, s, mesh)),
        np.asarray(ref.rmsnorm_ref(jnp.asarray(x), jnp.asarray(s))),
        atol=2e-5, rtol=1e-4)
    q = rng.normal(size=(2, 2, 16, 8)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(sharded_flash_attention(q, q, q, mesh,
                                           block_q=16, block_k=16)),
        np.asarray(ref.flash_attention_ref(jnp.asarray(q), jnp.asarray(q),
                                           jnp.asarray(q), causal=True)),
        atol=2e-5, rtol=1e-4)


def test_sharded_kernels_reject_indivisible_rows():
    # divisibility is checked before shard_map ever sees the mesh, so a
    # shape-only stand-in exercises the error path at any device count
    class _Mesh4:
        axis_names = ("data",)
        shape = {"data": 4}
    x = np.zeros((6, 8), np.float32)
    with pytest.raises(ValueError):
        sharded_kd_loss(x, x, np.zeros((6,), np.int32), _Mesh4())


# ------------------------------------------------------------------ #
# sharding-rule selection on the CNN pool
# ------------------------------------------------------------------ #

class _FakeMesh:
    axis_names = ("data", "model")
    shape = {"data": 4, "model": 4}


def test_cnn_pool_param_rules():
    """launch/sharding.py's name-based rules on the CNN pool: conv stacks
    and biases replicated, fc1 column-parallel, fc2 row-parallel — and on
    the cohort engine's (1-model-axis) debug mesh everything falls back
    to replicated, matching the engine's replicated-globals layout."""
    pool = cnn_pool("mnist")
    params = init_cnn(jax.random.PRNGKey(0), pool["large"])
    mesh = _FakeMesh()

    def spec_of(name, leaf):
        return param_pspec((jax.tree_util.DictKey(name),), leaf, mesh)

    for w in params["conv"]:
        assert spec_of("conv", w) == P(None, None, None, None)
    for b in params["conv_b"]:
        assert spec_of("conv_b", b) == P(None)
    fc1 = params["fc1"]       # (flat, hidden): col-parallel when divisible
    want_fc1 = P("data" if fc1.shape[0] % 4 == 0 else None,
                 "model" if fc1.shape[1] % 4 == 0 else None)
    assert spec_of("fc1", fc1) == want_fc1
    fc2 = params["fc2"]       # (hidden, classes=10): 10 % 4 != 0 -> unsharded
    assert spec_of("fc2", fc2) == P("model" if fc2.shape[0] % 4 == 0
                                    else None, None)


# ------------------------------------------------------------------ #
# true multi-device parity (subprocess, forced host device count)
# ------------------------------------------------------------------ #

MESH_PARITY_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import numpy as np
from repro.fl import FLEnvironment, FLSimConfig, HAPFLServer, \\
    BatchedClientEngine
from repro.fl.sharded import ShardedClientEngine
from repro.launch.mesh import make_debug_mesh

assert len(jax.devices()) == 4, jax.devices()
CFG = FLSimConfig(dataset="mnist", n_train=400, n_test=100,
                  batches_per_epoch=1, default_epochs=2,
                  n_clients=6, k_per_round=4, size_names=("small", "large"))
clients = [0, 1, 2, 3]
sizes = ["small", "small", "large", "large"]
intensities = [1, 3, 2, 1]
srv = HAPFLServer(FLEnvironment(CFG), seed=0)
ref = BatchedClientEngine(FLEnvironment(CFG)).train_cohort(
    clients, sizes, intensities, srv.global_by_size, srv.lite_params)
for n in (1, 2, 4):
    eng = ShardedClientEngine(FLEnvironment(CFG), mesh=make_debug_mesh(n))
    assert eng.n_shards == n
    got = eng.train_cohort(clients, sizes, intensities,
                           srv.global_by_size, srv.lite_params)
    for tr, tg in zip(ref, got):
        for lr, lg in zip(jax.tree_util.tree_leaves(tr),
                          jax.tree_util.tree_leaves(tg)):
            np.testing.assert_allclose(np.asarray(lr), np.asarray(lg),
                                       atol=1e-5, rtol=1e-4)
    # pad-invariance on the multi-device mesh: ragged 2-client group, each
    # side on fresh loaders (eng's have already drawn client 1's batches)
    exact = ShardedClientEngine(FLEnvironment(CFG),
                                mesh=make_debug_mesh(n)).train_cohort(
        [1, 4], ["small", "small"], [1, 3],
        srv.global_by_size, srv.lite_params, pad_pow2=False)
    padded = ShardedClientEngine(FLEnvironment(CFG),
                                 mesh=make_debug_mesh(n)).train_cohort(
        [1, 4], ["small", "small"], [1, 3],
        srv.global_by_size, srv.lite_params)
    for tp, te in zip(padded, exact):
        for lp, le in zip(jax.tree_util.tree_leaves(tp),
                          jax.tree_util.tree_leaves(te)):
            np.testing.assert_allclose(np.asarray(lp), np.asarray(le),
                                       atol=1e-5, rtol=1e-4)
print("OK")
"""


@pytest.mark.slow
def test_mesh_parity_across_device_counts_subprocess():
    """Sharded-vs-single-device parity and pad-invariance across mesh
    sizes 1/2/4 under a real forced 4-device host (subprocess so the main
    test process keeps its single-device view)."""
    res = subprocess.run([sys.executable, "-c", MESH_PARITY_SNIPPET],
                         capture_output=True, text=True, timeout=900,
                         env={**__import__("os").environ,
                              "PYTHONPATH": "src"})
    assert res.returncode == 0, res.stderr[-3000:]
    assert "OK" in res.stdout
