"""Batched multi-client training engine: vmap over clients, scan over steps.

The sequential engine (HAPFLServer._client_train) dispatches one jitted step
per (client, batch) — `k * intensity * batches_per_epoch` XLA calls per
round, each on a tiny batch, so Python/dispatch overhead dominates and
wall-clock grows linearly with cohort size. This engine instead:

  1. groups the round's cohort by (model-size category, loader batch size)
     — clients in a group share an architecture, so their parameter pytrees
     stack into (clients, ...) arrays;
  2. prefetches each client's full step sequence of iid batches in one
     vectorized rng draw (`data.pipeline.prefetch_steps`), zero-padding
     ragged per-client intensities to a power-of-two step count S;
  3. runs ONE jitted `jax.vmap`-over-clients of a `jax.lax.scan`-over-steps
     mutual-KD train step per group. Padded steps are computed but their
     updates are discarded with `jnp.where` on the (clients, S) step mask,
     so ragged intensities stay exact.

Because `sample_many` reproduces `sample()`'s rng stream element-for-element
and masked steps never touch parameters, the engine matches the sequential
path to float tolerance (tests/test_batched.py asserts it).

Step counts are padded to the next power of two so XLA compiles O(log
max_steps) distinct shapes per group size instead of one per intensity
pattern.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distill import make_mutual_train_fns
from repro.models.cnn import apply_cnn_fast
from repro.obs.trace import current as _tracer


def next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def masked_select(new, old, keep):
    """Pytree-wise jnp.where(keep, new, old) — drops a masked step's update."""
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(keep, a, b), new, old)


def make_train_one(raw_step, init_opt, unroll: int = 4):
    """One client's (params, xs, ys, mask) -> trained params: a scan over the
    prefetched step sequence with masked-step updates dropped. The shared
    building block of the batched (vmap) and sharded (vmap-under-mesh)
    trainers — both engines run EXACTLY this per-client computation, which
    is why their parity is a property, not a tolerance hunt.
    `unroll` partially unrolls the step scan — XLA CPU loses intra-op
    parallelism inside while-loop bodies, so straight-lining a few steps
    recovers it at modest compile cost."""
    def train_one(params, xs, ys, mask):
        opt_state = init_opt(params)

        def body(carry, inp):
            p, o = carry
            x, y, m = inp
            p2, o2, _ = raw_step(p, o, x, y)
            return (masked_select(p2, p, m), masked_select(o2, o, m)), None

        (params, _), _ = jax.lax.scan(body, (params, opt_state),
                                      (xs, ys, mask),
                                      unroll=min(unroll, xs.shape[0]))
        return params

    return train_one


def cohort_step_fns(cfg, lite_cfg, lr: float):
    """Un-jitted mutual-KD (step, init_opt) of one size group: the `cfg`
    local model paired with the `lite_cfg` LiteModel. apply_cnn_fast
    (im2col convs + slice pooling) is numerically equivalent to apply_cnn
    but efficient under vmap on CPU."""
    return make_mutual_train_fns(
        functools.partial(lambda p, x, cc: apply_cnn_fast(p, cc, x), cc=cfg),
        functools.partial(lambda p, x, cc: apply_cnn_fast(p, cc, x),
                          cc=lite_cfg),
        lr=lr)


def make_batched_trainer(raw_step, init_opt, unroll: int = 4):
    """Compile (stacked_params, xs, ys, mask) -> trained stacked_params.

    raw_step/init_opt are the un-jitted fns from make_mutual_train_fns.
    Shapes: xs (C, S, B, ...), ys (C, S, B), mask (C, S) bool; params leaves
    carry a leading client axis C. One XLA dispatch trains the whole group.
    """
    return jax.jit(jax.vmap(make_train_one(raw_step, init_opt, unroll)))


def scan_train(raw_step, init_opt):
    """Single-model analogue for the baselines: scan one client's prefetched
    (xs, ys, mask) through a plain-CE step (extra `global_params` arg is the
    FedProx anchor). Returns a jitted (params, xs, ys, mask, gp) -> params."""
    def run(params, xs, ys, mask, global_params):
        opt_state = init_opt(params)

        def body(carry, inp):
            p, o = carry
            x, y, m = inp
            p2, o2, _ = raw_step(p, o, x, y, global_params)
            return (masked_select(p2, p, m), masked_select(o2, o, m)), None

        (params, _), _ = jax.lax.scan(body, (params, opt_state),
                                      (xs, ys, mask))
        return params

    return jax.jit(run)


class BatchedClientEngine:
    """Trains a whole HAPFL cohort in one dispatch per size group.

    Built once per server; reuses jit caches across rounds (recompiles only
    when a group's (clients, padded-steps) shape is new).
    """

    def __init__(self, env, lr: float = None):
        self.env = env
        lr = env.cfg.lr if lr is None else lr
        self._trainers = {
            s: self._build_trainer(*cohort_step_fns(c, env.lite_cfg, lr))
            for s, c in env.pool.items()}

    # hooks the mesh-sharded subclass (fl/sharded.py) overrides ---------- #
    def _build_trainer(self, raw_step, init_opt):
        return make_batched_trainer(raw_step, init_opt)

    def _client_pad(self, n: int) -> int:
        """Padded client-axis length for an n-client group."""
        return max(next_pow2(n), 4)

    def _dispatch(self, size: str, start, xs, ys, mask):
        """Run one size group's trainer. `start` is the unstacked {local,
        lite} param pytree; data arrays carry the padded client axis."""
        stacked = jax.tree_util.tree_map(
            lambda p: jnp.broadcast_to(p, (xs.shape[0],) + p.shape), start)
        return self._trainers[size](stacked, jnp.asarray(xs),
                                    jnp.asarray(ys), jnp.asarray(mask))

    def _group_label(self, size: str, Cp: int, S: int) -> str:
        return f"train_cohort[{size}]x{Cp}s{S}"

    def train_cohort(self, clients: Sequence[int], sizes: Sequence[str],
                     intensities: Sequence[int], global_by_size: Dict,
                     lite_params, pad_pow2: bool = True,
                     pad_clients: bool = True) -> List[Dict]:
        """Run every client's {local, lite} mutual-KD training; returns
        per-client params dicts aligned with the input order.

        Ragged intensities are handled by bucketing: within a (size, batch)
        group, clients whose step counts share a pow2 ceiling train together
        (masked-step waste < 2x; padding everyone to the cohort max would
        waste up to max/mean). PPO1/PPO2 reshuffle group shapes every round,
        so the client axis is additionally padded to the next pow2 (min 4)
        with fully-masked dummy clients (zero data, loader rngs untouched) —
        the engine compiles O(log k * log max_steps) distinct XLA shapes per
        size over a whole run, then runs from cache."""
        env = self.env
        bpe = env.cfg.batches_per_epoch
        out: List = [None] * len(clients)
        groups: Dict = {}
        for i, (c, s) in enumerate(zip(clients, sizes)):
            sb = next_pow2(int(intensities[i]) * bpe) if pad_pow2 else 0
            groups.setdefault((s, env.loaders[c].batch_size, sb), []).append(i)
        for (s, _, _), idx in groups.items():
            steps = [int(intensities[i]) * bpe for i in idx]
            S = next_pow2(max(steps)) if pad_pow2 else max(steps)
            xs, ys, mask = env.prefetch_round([clients[i] for i in idx],
                                              steps, pad_to=S)
            C = len(idx)
            Cp = self._client_pad(C) if pad_clients else C
            if Cp > C:
                pad = Cp - C
                xs = np.concatenate(
                    [xs, np.zeros((pad,) + xs.shape[1:], xs.dtype)])
                ys = np.concatenate(
                    [ys, np.zeros((pad,) + ys.shape[1:], ys.dtype)])
                mask = np.concatenate(
                    [mask, np.zeros((pad,) + mask.shape[1:], mask.dtype)])
            start = {"local": global_by_size[s], "lite": lite_params}
            # names the group's vmap+scan dispatch both in our tracer (wall
            # span) and in any active jax.profiler trace
            with _tracer().annotation(self._group_label(s, Cp, S)):
                trained = self._dispatch(s, start, xs, ys, mask)
                # one device->host transfer per group; per-client numpy
                # views avoid spawning ~10 device slice ops per client
                host = jax.device_get(trained)
            for j, i in enumerate(idx):
                out[i] = jax.tree_util.tree_map(lambda a: a[j], host)
        return out
