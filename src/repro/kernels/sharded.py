"""Mesh-sharded wrappers for the Pallas kernels (row/batch data-parallel).

Each wrapper `shard_map`s the corresponding `ops.py` kernel over the
`data` axis of a mesh: the leading axis (logit rows for kd_loss/rmsnorm,
batch for flash_attention) is split into per-device shards and every
device runs the *actual Pallas kernel body* (interpret mode on the CPU
backend, see docs/kernels.md §2) on its shard. All three ops are row-independent, so
the sharded programs contain no collectives and agree with the
single-device kernels exactly (pinned in tests/test_sharded.py).

This is the same layout the sharded cohort engine (fl/sharded.py) uses
for the client axis, so the kernels slot onto its hot path unchanged:
`bench_mesh.py` times `sharded_kd_loss` per shard and the roofline
discussion in docs/kernels.md cites those numbers.
"""
from __future__ import annotations

import functools

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.ops import flash_attention_op, kd_loss_op, rmsnorm_op
from repro.obs.trace import current as _tracer


def _check_divisible(n: int, mesh: Mesh, axis: str, what: str) -> None:
    shards = mesh.shape[axis]
    if n % shards:
        raise ValueError(f"{what}={n} not divisible by mesh {axis!r} "
                         f"axis size {shards}")


def sharded_kd_loss(x_logits, y_logits, labels, mesh: Mesh,
                    axis: str = "data", *, block_n: int = 256,
                    block_v: int = 512):
    """(N, V) x 2 + (N,) labels -> per-row KD terms, rows split over the
    mesh. N must divide by the axis size; each shard's N/shards rows must
    satisfy the kernel's own row-block constraint (block_n is clamped to
    the shard size, so pow2 shard sizes always work)."""
    _check_divisible(x_logits.shape[0], mesh, axis, "rows")
    fn = jax.shard_map(
        functools.partial(kd_loss_op, block_n=block_n, block_v=block_v),
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis)),
        out_specs=P(axis), check_vma=False)
    with _tracer().annotation(f"sharded.kd_loss@{mesh.shape[axis]}"):
        return fn(x_logits, y_logits, labels)


def sharded_rmsnorm(x, scale, mesh: Mesh, axis: str = "data", *,
                    block_n: int = 256, eps: float = 1e-5):
    """(N, D) row-sharded rmsnorm; the (D,) scale is replicated."""
    _check_divisible(x.shape[0], mesh, axis, "rows")
    fn = jax.shard_map(
        functools.partial(rmsnorm_op, block_n=block_n, eps=eps),
        mesh=mesh, in_specs=(P(axis, None), P(None)),
        out_specs=P(axis, None), check_vma=False)
    with _tracer().annotation(f"sharded.rmsnorm@{mesh.shape[axis]}"):
        return fn(x, scale)


def sharded_flash_attention(q, k, v, mesh: Mesh, axis: str = "data", *,
                            causal: bool = True, sliding_window: int = 0,
                            block_q: int = 128, block_k: int = 128):
    """(B, H, S, hd) attention with the batch axis split over the mesh."""
    _check_divisible(q.shape[0], mesh, axis, "batch")
    spec = P(axis, None, None, None)
    fn = jax.shard_map(
        functools.partial(flash_attention_op, causal=causal,
                          sliding_window=sliding_window,
                          block_q=block_q, block_k=block_k),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    with _tracer().annotation(f"sharded.flash_attention@{mesh.shape[axis]}"):
        return fn(q, k, v)
