"""Mesh-sharded cohort engine: client-data-parallel batched training.

`BatchedClientEngine` (fl/batched.py) trains a whole size group in one
vmap-over-clients dispatch — on ONE device. This engine partitions that
dispatch's client axis over the `data` axis of a `jax.sharding.Mesh`
(`launch/mesh.py`), the natural data-parallel axis in federated learning:
every client's mutual-KD scan is independent of every other client's, so
the sharded program contains **zero collectives** — each device trains
its contiguous slice of the padded client axis and the only cross-device
traffic is the final result gather back to host.

Layout (DESIGN.md §17, docs/sharding.md):

  - data arrays  xs (C, S, B, ...), ys (C, S, B), mask (C, S):
      NamedSharding(mesh, P("data"))   — client axis split across devices
  - start params {local, lite} (unstacked):
      NamedSharding(mesh, P())         — replicated; the per-client stack
      is broadcast *inside* the jitted program, so each device
      materializes only its own slice of the (C, ...) stacked params
  - trained output: P("data") on the leading client axis, like the data.

Cross-size cohorts never share a dispatch (their pytrees cannot stack);
each size group is its own mesh-wide sharded program, dispatched
sequentially — "separate mesh slices" in time, the full `data` axis each.

The client axis is padded to pow2 (shape-cache discipline inherited from
the batched engine) AND up to a multiple of the mesh's data-axis size, so
every device holds the same number of (possibly fully-masked) clients —
`pad_to_mesh` below is the invariant, pinned in tests/test_sharded.py.

On chips, `launch.mesh.make_debug_mesh(n)` builds the (data, model) mesh
over the first n devices, all in one process (`chip_smoke.py --chips 4`
runs this engine over four v5e chips against the batched engine on one).
The CPU tests simulate devices instead:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 python ...

(the flag must be set before jax initializes, so those tests and the CPU
form of bench_mesh use subprocesses).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.fl.batched import (BatchedClientEngine, make_train_one, next_pow2)
from repro.launch.mesh import make_debug_mesh


def pad_to_mesh(n: int, n_shards: int) -> int:
    """Padded client-axis length: next_pow2 (min 4, the batched engine's
    shape-cache discipline) rounded up to a multiple of the mesh data-axis
    size so every device gets an equal client slice. For pow2 device
    counts (the usual case) the rounding is a no-op once pow2(n) >= shards."""
    c = max(next_pow2(n), 4)
    return c if c % n_shards == 0 else ((c + n_shards - 1) // n_shards) * n_shards


def make_sharded_trainer(raw_step, init_opt, mesh: Mesh, axis: str = "data",
                         unroll: int = 4):
    """Compile (start_params, xs, ys, mask) -> trained stacked params, with
    the client axis of xs/ys/mask/output split over `mesh`'s `axis` and
    `start_params` replicated. The per-client body is make_train_one — the
    very computation the single-device batched trainer vmaps — so sharded
    and batched results agree to float tolerance by construction."""
    train_one = make_train_one(raw_step, init_opt, unroll)
    repl = NamedSharding(mesh, P())
    shard = NamedSharding(mesh, P(axis))

    def train_group(start, xs, ys, mask):
        stacked = jax.tree_util.tree_map(
            lambda p: jnp.broadcast_to(p, (xs.shape[0],) + p.shape), start)
        return jax.vmap(train_one)(stacked, xs, ys, mask)

    return jax.jit(train_group,
                   in_shardings=(repl, shard, shard, shard),
                   out_shardings=shard)


class ShardedClientEngine(BatchedClientEngine):
    """BatchedClientEngine with every size-group dispatch partitioned over
    a device mesh. Drop-in: `train_cohort` has the identical signature and
    returns per-client params in input order; `HAPFLServer(engine="sharded",
    mesh=...)` routes through it interchangeably with the batched and
    sequential engines (parity pinned in tests/test_sharded.py)."""

    def __init__(self, env, mesh: Optional[Mesh] = None, lr: float = None,
                 axis: str = "data"):
        # default: a (n_devices, 1) debug mesh over whatever devices exist
        self.mesh = mesh if mesh is not None else make_debug_mesh()
        if axis not in self.mesh.axis_names:
            raise ValueError(f"mesh has no {axis!r} axis "
                             f"(axes: {self.mesh.axis_names})")
        self.axis = axis
        self.n_shards = int(self.mesh.shape[axis])
        super().__init__(env, lr=lr)

    def _build_trainer(self, raw_step, init_opt):
        return make_sharded_trainer(raw_step, init_opt, self.mesh, self.axis)

    def _client_pad(self, n: int) -> int:
        return pad_to_mesh(n, self.n_shards)

    def _dispatch(self, size: str, start, xs, ys, mask):
        # jit's in_shardings place the host arrays: data split on the client
        # axis, start params replicated (broadcast to the per-client stack
        # happens inside the program, on-shard)
        return self._trainers[size](start, jnp.asarray(xs), jnp.asarray(ys),
                                    jnp.asarray(mask))

    def _group_label(self, size: str, Cp: int, S: int) -> str:
        return (f"train_cohort[{size}]x{Cp}s{S}"
                f"@mesh{self.axis}={self.n_shards}")
