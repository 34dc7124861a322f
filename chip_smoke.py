#!/usr/bin/env python3
"""Chip smoke run: the HAPFL training path, once, on a TPU.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # mesh-sharded engine over four chips

One chip runs three phases in one process:

  main       FLEnvironment -> HAPFLServer (engine "auto" -> "batched") ->
             cohort engine -> aggregation, at the paper's Table II settings
             on the widest CNN pool (imagenet10: 64x64x3, large =
             32/64/128 channels): latency-only RL warm-up rounds, then
             rounds of real mutual-KD training and evaluation.
  reference  one mixed-size ragged cohort trained by the batched engine on
             the chip and by the sequential engine on the CPU backend of
             this process, compared at "highest" matmul precision.
  kernels    each Pallas kernel once at a real shape: compiled (its
             lowered program holds a tpu_custom_call) and equal to ref.py.

``--chips 4`` runs only the sharded engine over 1/2/4-chip meshes against
the batched engine on one chip, and one HAPFLServer(mesh=...) round.

Exits non-zero when JAX finds no TPU. The last line of stdout is one JSON
object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Phase sizes. The main path keeps the Table II defaults of FLSimConfig
# (K=10, k=6, E=20, batch 32) on the imagenet10 pool, but not its client
# lr: the 5e-3 default (tuned on mnist) diverges there, with the loss
# rising from 5 to 247 within 5 SGD steps (on the CPU too), and on a chip
# one client reached |theta| ~ 6e7 and the next round went NaN. At 1e-3
# the same clients train stably.
MAIN_DATASET = "imagenet10"
MAIN_LR = 1e-3
# a diverging client shows as parameters this large long before a NaN;
# healthy trained CNN weights here stay below 10
MAX_ABS_PARAM = 1e3
RL_ROUNDS = 20
TRAIN_ROUNDS = 3
# the cohort of tests/test_batched.py: 2 sizes, ragged intensities
REF_CFG = dict(dataset="mnist", n_train=400, n_test=100, batches_per_epoch=1,
               default_epochs=2, n_clients=6, k_per_round=4,
               size_names=("small", "large"))
REF_COHORT = ([0, 1, 2, 3], ["small", "small", "large", "large"],
              [1, 3, 2, 1])
# batched (im2col convs) vs sequential (lax.conv) at "highest" precision:
# 10x the CPU tests' 1e-5/1e-4, for the TPU's multi-pass f32 matmuls and
# its own exp/log
REF_TOL = dict(atol=1e-4, rtol=1e-3)
# sharded vs batched run the same per-client program: the CPU tests' bound
MESH_TOL = dict(atol=1e-5, rtol=1e-4)
KD_SHAPE = (4096, 32768)            # (rows, vocab) f32
RMS_SHAPE = (4096, 4096)            # (rows, d) bf16
FLASH_SHAPE = (1, 8, 2048, 128)     # (B, H, S, hd) bf16
FLASH_WINDOW = 512


def log(msg) -> None:
    print(msg if isinstance(msg, str) else json.dumps(msg), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {what}")


class CompileMeter:
    """Backend compile seconds (persistent-cache reads included) and
    persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.secs, self.compiles, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.secs, self.compiles, self.hits

    def since(self, snap) -> dict:
        s, c, h = snap
        return {"compile_s": round(self.secs - s, 3),
                "compiles": self.compiles - c, "cache_hits": self.hits - h}


def _leaves(tree):
    import jax
    import numpy as np
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def max_abs_diff(a, b) -> float:
    import numpy as np
    return max(float(np.max(np.abs(x - y)))
               for x, y in zip(_leaves(a), _leaves(b)))


def max_abs(*trees) -> float:
    import numpy as np
    return max(float(np.max(np.abs(x))) for t in trees for x in _leaves(t))


def trees_close(a, b, atol: float, rtol: float) -> bool:
    import numpy as np
    return all(np.allclose(x, y, atol=atol, rtol=rtol)
               for x, y in zip(_leaves(a), _leaves(b)))


def all_finite(tree) -> bool:
    import numpy as np
    return all(np.isfinite(x).all() for x in _leaves(tree))


def platforms(tree) -> list:
    """Platforms holding the tree's leaves ("host" for a numpy leaf)."""
    import jax
    out = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        devs = getattr(leaf, "devices", None)
        out |= {d.platform for d in devs()} if devs else {"host"}
    return sorted(out)


def require_tpu(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: needs {chips} chips; JAX found "
                         f"{len(devs)}")
    log(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}")
    return devs


# ------------------------------------------------------------------ #
# phases
# ------------------------------------------------------------------ #

def phase_main(meter, platform: str, cfg) -> None:
    from repro.fl import FLEnvironment, HAPFLServer
    snap, t0 = meter.snapshot(), time.perf_counter()
    srv = HAPFLServer(FLEnvironment(cfg), seed=0)
    check(srv.engine == "batched", f"engine auto resolved to {srv.engine!r}")
    log({"phase": "main.setup", "dataset": cfg.dataset,
         "pool": {s: [list(c.in_shape), list(c.channels), c.hidden]
                  for s, c in srv.env.pool.items()},
         "K": cfg.n_clients, "k": cfg.k_per_round, "E": cfg.default_epochs,
         "batch": cfg.batch_size, "lr": cfg.lr, "engine": srv.engine,
         "wall_s": round(time.perf_counter() - t0, 3)})
    snap, t0 = meter.snapshot(), time.perf_counter()
    hist = srv.pretrain_rl(RL_ROUNDS)
    check(all(math.isfinite(h["straggling"]) for h in hist),
          "non-finite straggling latency in RL warm-up")
    log({"phase": "main.pretrain_rl", "rounds": RL_ROUNDS,
         "straggling_first": hist[0]["straggling"],
         "straggling_last": hist[-1]["straggling"],
         "wall_s": round(time.perf_counter() - t0, 3), **meter.since(snap)})
    for _ in range(TRAIN_ROUNDS):
        snap, t0 = meter.snapshot(), time.perf_counter()
        rec = srv.run_round()
        wall = time.perf_counter() - t0
        acc_local = [a["local"] for a in rec.client_acc.values()]
        acc_lite = [a["lite"] for a in rec.client_acc.values()]
        where = platforms(srv.global_by_size)
        biggest = max_abs(srv.global_by_size, srv.lite_params)
        log({"phase": "main.round", "round": rec.round_idx,
             "sizes": rec.sizes, "intensities": rec.intensities,
             "wall_s": round(wall, 3), "straggling": rec.straggling,
             "acc_local": acc_local, "acc_lite": acc_lite,
             "acc_lite_global": rec.acc_lite, "acc_by_size": rec.acc_by_size,
             "max_abs_param": biggest, "global_by_size_on": where,
             **meter.since(snap)})
        check(all(math.isfinite(v) for v in
                  [rec.straggling, rec.acc_lite, *acc_local, *acc_lite,
                   *rec.acc_by_size.values()]), "non-finite round metric")
        check(all_finite(srv.global_by_size) and all_finite(srv.lite_params),
              "non-finite global parameters")
        check(biggest < MAX_ABS_PARAM,
              f"global parameters diverging: max |theta| = {biggest:.3g}")
        check(where == [platform], f"globals on {where}, not {platform}")


def phase_reference(meter, device, cpu) -> None:
    import jax
    from repro.fl import BatchedClientEngine, FLEnvironment, FLSimConfig, \
        HAPFLServer
    cfg = FLSimConfig(**REF_CFG)
    clients, sizes, intensities = REF_COHORT
    snap, t0 = meter.snapshot(), time.perf_counter()
    with jax.default_device(cpu):
        ref = HAPFLServer(FLEnvironment(cfg), seed=5, engine="sequential")
        seq = jax.device_get([ref._client_train(c, s, t) for c, s, t in
                              zip(clients, sizes, intensities)])
    log({"phase": "reference.sequential_cpu",
         "wall_s": round(time.perf_counter() - t0, 3), **meter.since(snap)})
    start = jax.device_put((ref.global_by_size, ref.lite_params), device)
    diffs = {}
    for precision in ("highest", None):
        snap, t0 = meter.snapshot(), time.perf_counter()
        eng = BatchedClientEngine(FLEnvironment(cfg))
        with jax.default_matmul_precision(precision):
            bat = eng.train_cohort(clients, sizes, intensities, *start)
        name = precision or "default"
        diffs[name] = max(max_abs_diff(s, b) for s, b in zip(seq, bat))
        log({"phase": "reference.batched", "precision": name,
             "max_abs_diff_vs_sequential": diffs[name],
             "wall_s": round(time.perf_counter() - t0, 3),
             **meter.since(snap)})
        if precision == "highest":
            close = all(trees_close(s, b, **REF_TOL)
                        for s, b in zip(seq, bat))
    check(close, f"batched vs sequential at highest precision: max abs "
                 f"diff {diffs['highest']:.3g} outside {REF_TOL}")


def _kernel_cases(key):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.ops import flash_attention_op, kd_loss_op, rmsnorm_op
    k = jax.random.split(key, 6)
    n, v = KD_SHAPE
    kd_args = (jax.random.normal(k[0], (n, v), jnp.float32),
               jax.random.normal(k[1], (n, v), jnp.float32),
               jax.random.randint(k[2], (n,), 0, v))
    rn, d = RMS_SHAPE
    rms_args = (jax.random.normal(k[3], (rn, d), jnp.bfloat16),
                1.0 + 0.1 * jax.random.normal(k[4], (d,), jnp.float32))
    qkv = tuple(jax.random.normal(kk, FLASH_SHAPE, jnp.bfloat16)
                for kk in jax.random.split(k[5], 3))
    # f32 kd terms: reduction order over the vocab; bf16 outputs: ~2 ulp
    f32_tol, bf16_tol = dict(atol=1e-3, rtol=1e-3), dict(atol=2e-2, rtol=2e-2)
    return [
        ("kd_loss", kd_loss_op, ref.kd_loss_ref, kd_args, f32_tol),
        ("rmsnorm", rmsnorm_op, ref.rmsnorm_ref, rms_args, bf16_tol),
        ("flash_attention_causal",
         lambda q, k_, v_: flash_attention_op(q, k_, v_, causal=True),
         lambda q, k_, v_: ref.flash_attention_ref(q, k_, v_, causal=True),
         qkv, bf16_tol),
        ("flash_attention_window",
         lambda q, k_, v_: flash_attention_op(
             q, k_, v_, causal=True, sliding_window=FLASH_WINDOW),
         lambda q, k_, v_: ref.flash_attention_ref(
             q, k_, v_, causal=True, sliding_window=FLASH_WINDOW),
         qkv, bf16_tol),
    ]


def phase_kernels(meter) -> None:
    import jax
    import numpy as np
    for name, op, ref_fn, args, tol in _kernel_cases(jax.random.PRNGKey(0)):
        snap, t0 = meter.snapshot(), time.perf_counter()
        lowered = jax.jit(op).lower(*args)
        compiled_kernel = "tpu_custom_call" in lowered.as_text()
        got = jax.block_until_ready(lowered.compile()(*args))
        want = jax.jit(ref_fn)(*args)
        got_l, want_l = _leaves(got), _leaves(want)
        err = max(float(np.max(np.abs(g.astype(np.float32)
                                      - w.astype(np.float32))))
                  for g, w in zip(got_l, want_l))
        ok = all(np.allclose(g.astype(np.float32), w.astype(np.float32),
                             **tol) for g, w in zip(got_l, want_l))
        log({"phase": "kernels", "kernel": name,
             "shapes": [list(a.shape) for a in args],
             "tpu_custom_call": compiled_kernel, "max_abs_err_vs_ref": err,
             "tol": tol, "wall_s": round(time.perf_counter() - t0, 3),
             **meter.since(snap)})
        check(compiled_kernel,
              f"{name}: no tpu_custom_call in the lowered program")
        check(ok and all(np.isfinite(g).all() for g in got_l),
              f"{name}: max abs err {err:.3g} vs ref.py outside {tol}")


def phase_mesh(meter, platform: str) -> None:
    """Sharded engine over 1/2/4-chip meshes == batched engine on one
    chip (the MESH_PARITY_SNIPPET cohort of tests/test_sharded.py), then
    one HAPFLServer(mesh=...) round on the full mesh."""
    import jax
    from repro.fl import BatchedClientEngine, FLEnvironment, FLSimConfig, \
        HAPFLServer, ShardedClientEngine
    from repro.launch.mesh import make_debug_mesh

    class KeepStack(ShardedClientEngine):
        """Keeps each group's trained stack before it leaves the device."""
        def _dispatch(self, *args):
            self.stacks.append(super()._dispatch(*args))
            return self.stacks[-1]

    cfg = FLSimConfig(**REF_CFG)
    clients, sizes, intensities = REF_COHORT
    srv = HAPFLServer(FLEnvironment(cfg), seed=0)
    start = (srv.global_by_size, srv.lite_params)
    with jax.default_matmul_precision("highest"):
        ref = BatchedClientEngine(FLEnvironment(cfg)).train_cohort(
            clients, sizes, intensities, *start)
    failures = []
    for n in (1, 2, 4):
        snap, t0 = meter.snapshot(), time.perf_counter()
        mesh = make_debug_mesh(n)
        eng = KeepStack(FLEnvironment(cfg), mesh=mesh)
        eng.stacks = []
        # pad invariance on a ragged 2-client group, each side on fresh
        # loaders
        pad_case = ([1, 4], ["small", "small"], [1, 3], *start)
        with jax.default_matmul_precision("highest"):
            got = eng.train_cohort(clients, sizes, intensities, *start)
            padded = ShardedClientEngine(FLEnvironment(cfg), mesh=mesh
                                         ).train_cohort(*pad_case)
            exact = ShardedClientEngine(FLEnvironment(cfg), mesh=mesh
                                        ).train_cohort(*pad_case,
                                                       pad_pow2=False)
        mesh_devs = set(mesh.devices.flat)
        spans = all(leaf.sharding.device_set == mesh_devs
                    and len(leaf.addressable_shards) == n
                    and all(s.data.shape[0] == leaf.shape[0] // n
                            for s in leaf.addressable_shards)
                    for st in eng.stacks
                    for leaf in jax.tree_util.tree_leaves(st))
        diff = max(max_abs_diff(r, g) for r, g in zip(ref, got))
        pad_diff = max(max_abs_diff(p, e) for p, e in zip(padded, exact))
        log({"phase": "mesh.parity", "devices": n,
             "max_abs_diff_vs_batched": diff, "pad_invariance_diff": pad_diff,
             "stack_spans_mesh": spans, "groups": len(eng.stacks),
             "wall_s": round(time.perf_counter() - t0, 3),
             **meter.since(snap)})
        if not (all(trees_close(r, g, **MESH_TOL) for r, g in zip(ref, got))
                and all(trees_close(p, e, **MESH_TOL)
                        for p, e in zip(padded, exact)) and spans):
            failures.append(n)
    snap, t0 = meter.snapshot(), time.perf_counter()
    msrv = HAPFLServer(FLEnvironment(cfg), seed=3,
                       mesh=make_debug_mesh(4))
    rec = msrv.run_round()
    log({"phase": "mesh.server_round", "devices": 4,
         "engine": msrv.engine, "sizes": rec.sizes,
         "intensities": rec.intensities, "acc_lite_global": rec.acc_lite,
         "global_by_size_on": platforms(msrv.global_by_size),
         "wall_s": round(time.perf_counter() - t0, 3), **meter.since(snap)})
    check(not failures, f"sharded vs batched parity failed at {failures} "
                        f"devices (tolerance {MESH_TOL})")
    check(msrv.engine == "sharded" and math.isfinite(rec.acc_lite)
          and all_finite(msrv.global_by_size), "mesh server round")
    check(platforms(msrv.global_by_size) == [platform],
          "mesh server globals left the chip")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh-sharded engine path")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    devs = require_tpu(args.chips)
    import jax
    log(f"compile cache: {cache_dir}")
    meter = CompileMeter()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(meter, devs[0].platform)
    else:
        from repro.fl import FLSimConfig
        phase_main(meter, devs[0].platform,
                   FLSimConfig(dataset=MAIN_DATASET, lr=MAIN_LR))
        phase_reference(meter, devs[0], jax.devices("cpu")[0])
        phase_kernels(meter)
    log({"phase": "total", "wall_s": round(time.perf_counter() - t0, 3),
         **meter.since((0.0, 0, 0))})
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
