"""Compile the main path for a described TPU v5e (no chip attached).

The TPU compiler is installed with JAX, and it compiles for a topology
that is described rather than attached. These tests compile the Pallas
kernels, the batched cohort trainer and the mesh-sharded trainer at real
widths, so a kernel the chip would refuse (tiling, VMEM), a cohort
program that does not fit HBM, or a collective in the zero-collective
sharded program fails here without a chip. Nothing runs; results and
times come only from `chip_smoke.py` on the chip.

The topology is described inside module-scoped fixtures, never at import:
only one process may load the TPU library, and every test worker imports
this file. The persistent compilation cache is off around these compiles
(an entry compiled for a described chip cannot be read back without one).
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.fl.batched import cohort_step_fns, make_batched_trainer
from repro.fl.sharded import make_sharded_trainer
from repro.kernels.flash_attention import flash_attention
from repro.kernels.kd_loss import kd_loss
from repro.kernels.rmsnorm import rmsnorm
from repro.models.cnn import cnn_pool, init_cnn

V5E_HBM_BYTES = 15.75e9          # usable HBM the v5e compiler reports
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, sh):
    """(jitted kernel with interpret=False, argument specs) at the shapes
    chip_smoke.py runs."""
    if name == "kd_loss":
        n, v = 4096, 32768
        return (functools.partial(kd_loss, interpret=False),
                (_spec((n, v), jnp.float32, sh),
                 _spec((n, v), jnp.float32, sh), _spec((n,), jnp.int32, sh)))
    if name == "rmsnorm":
        return (functools.partial(rmsnorm, interpret=False),
                (_spec((4096, 4096), jnp.bfloat16, sh),
                 _spec((4096,), jnp.float32, sh)))
    qkv = tuple(_spec((1, 8, 2048, 128), jnp.bfloat16, sh) for _ in range(3))
    window = 512 if name == "flash_attention_window" else 0
    return (functools.partial(flash_attention, causal=True,
                              sliding_window=window, interpret=False), qkv)


@pytest.mark.parametrize("name", ["kd_loss", "rmsnorm",
                                  "flash_attention_causal",
                                  "flash_attention_window"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _group_specs(cfg, lite_cfg, clients, steps, batch, param_sh, data_sh,
                 stacked: bool):
    """Argument specs of one size group's trainer: {local, lite} params
    (with a leading client axis when `stacked`), xs, ys and the step mask."""
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: {"local": init_cnn(key, cfg),
                                     "lite": init_cnn(key, lite_cfg)})
    lead = (clients,) if stacked else ()
    params = jax.tree_util.tree_map(
        lambda a: _spec(lead + a.shape, a.dtype, param_sh), params)
    return (params,
            _spec((clients, steps, batch) + cfg.in_shape, jnp.float32,
                  data_sh),
            _spec((clients, steps, batch), jnp.int32, data_sh),
            _spec((clients, steps), jnp.bool_, data_sh))


def test_batched_trainer_fits_one_v5e(one_chip):
    """imagenet10-large at the paper's worst k=6 shape: PPO2 may give one
    client all E*k = 120 epochs -> 240 steps, padded to S=256, in a group
    padded to C=4 clients."""
    pool = cnn_pool("imagenet10")
    trainer = make_batched_trainer(
        *cohort_step_fns(pool["large"], pool["lite"], lr=5e-3))
    args = _group_specs(pool["large"], pool["lite"], clients=4, steps=256,
                        batch=32, param_sh=one_chip, data_sh=one_chip,
                        stacked=True)
    mem = trainer.lower(*args).compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, used


def test_sharded_trainer_has_no_collectives(mesh4):
    """The client axis split over 4 chips: every client trains alone, so
    the partitioned program holds no collective."""
    pool = cnn_pool("imagenet10")
    trainer = make_sharded_trainer(
        *cohort_step_fns(pool["large"], pool["lite"], lr=5e-3), mesh4)
    args = _group_specs(pool["large"], pool["lite"], clients=8, steps=64,
                        batch=32, param_sh=NamedSharding(mesh4, P()),
                        data_sh=NamedSharding(mesh4, P("data")),
                        stacked=False)
    hlo = trainer.lower(*args).compile().as_text()
    assert not [op for op in COLLECTIVES if op in hlo]
