"""jit'd public wrappers around the Pallas kernels.

On the CPU backend, which the tests force, kernels run with
``interpret=True`` (the kernel body executed as plain JAX ops) for
correctness validation. On any other backend they are compiled: a TPU
runs the Mosaic kernel (its lowered program holds a ``tpu_custom_call``),
and no accelerator falls back to interpret mode in silence.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.kd_loss import kd_loss as _kd
from repro.kernels.rmsnorm import rmsnorm as _rms
from repro.obs.trace import current as _tracer


def interpret_mode() -> bool:
    """True only on the CPU backend (the tests); accelerators compile."""
    return jax.default_backend() == "cpu"


def flash_attention_op(q, k, v, *, causal=True, sliding_window=0,
                       block_q=128, block_k=128):
    """q, k, v: (B, H, S, hd)."""
    with _tracer().annotation("pallas.flash_attention"):
        return _flash(q, k, v, causal=causal, sliding_window=sliding_window,
                      block_q=block_q, block_k=block_k,
                      interpret=interpret_mode())


def kd_loss_op(x_logits, y_logits, labels, *, block_n=256, block_v=512):
    """(N, V) x 2 + (N,) labels -> per-row {ce_x, ce_y, kl_xy, kl_yx}."""
    with _tracer().annotation("pallas.kd_loss"):
        return _kd(x_logits, y_logits, labels, block_n=block_n,
                   block_v=block_v, interpret=interpret_mode())


def rmsnorm_op(x, scale, *, block_n=256, eps=1e-5):
    with _tracer().annotation("pallas.rmsnorm"):
        return _rms(x, scale, block_n=block_n, eps=eps,
                    interpret=interpret_mode())


def mutual_kd_loss(x_logits, y_logits, labels, lambdas=(0.4, 0.6, 0.5, 0.5),
                   use_kernel: bool = False):
    """Paper Eqs. 33-34: L1 = l1*CE_x + l2*KL(x||sg(y)); L2 = l3*CE_y + l4*KL(y||sg(x)).

    Differentiable jnp path by default (training); kernel path for TPU eval.
    Logits may be (..., V); labels (...). Returns (L1+L2 scalar, metrics).
    """
    l1, l2, l3, l4 = lambdas
    V = x_logits.shape[-1]
    x = x_logits.reshape(-1, V)
    y = y_logits.reshape(-1, V)
    lab = labels.reshape(-1)
    if use_kernel:
        t = kd_loss_op(x, y, lab)
        ce_x, ce_y = t["ce_x"], t["ce_y"]
        kl_xy, kl_yx = t["kl_xy"], t["kl_yx"]
    else:
        sx = jax.lax.stop_gradient(x)
        sy = jax.lax.stop_gradient(y)
        tx = ref.kd_loss_ref(x, sy, lab)   # grads flow to x only
        ty = ref.kd_loss_ref(sx, y, lab)
        ce_x, kl_xy = tx["ce_x"], tx["kl_xy"]
        ce_y, kl_yx = ty["ce_y"], ty["kl_yx"]
    L1 = l1 * jnp.mean(ce_x) + l2 * jnp.mean(kl_xy)
    L2 = l3 * jnp.mean(ce_y) + l4 * jnp.mean(kl_yx)
    metrics = {"ce_local": jnp.mean(ce_x), "ce_lite": jnp.mean(ce_y),
               "kl_local_lite": jnp.mean(kl_xy), "kl_lite_local": jnp.mean(kl_yx)}
    return L1 + L2, metrics
