"""Single-host training driver: HAPFL joint-KD training of any assigned arch
at reduced scale (CPU) or, on real hardware, the full config under the
production mesh (same code path as the dry-run).

Example (CPU):
  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --steps 50 \
      --smoke --batch 4 --seq 128
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.data.synthetic import make_token_dataset
from repro.models.api import dummy_batch
from repro.train.step import (TrainStepConfig, make_hapfl_train_step,
                              make_train_state)


def token_batches(cfg, batch, seq, steps, seed=0):
    stream = make_token_dataset(cfg.vocab_size, batch * (seq + 1) * steps + 1,
                                seed)
    for i in range(steps):
        n = batch * (seq + 1)
        chunk = stream[i * n:(i + 1) * n].reshape(batch, seq + 1)
        if cfg.n_codebooks:
            t = np.stack([np.roll(chunk, q, -1) for q in
                          range(cfg.n_codebooks)], -1)
            yield {"tokens": jnp.asarray(t[:, :-1]),
                   "labels": jnp.asarray(t[:, 1:])}
        elif cfg.input_mode == "embeddings":
            b = dummy_batch(cfg, batch, seq, key=jax.random.PRNGKey(i))
            yield b
        else:
            yield {"tokens": jnp.asarray(chunk[:, :-1]),
                   "labels": jnp.asarray(chunk[:, 1:])}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-trainable ~100M-class)")
    ap.add_argument("--checkpoint", default="")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    lite = cfg.lite()
    if args.smoke:
        lite = dataclasses.replace(lite, dtype=jnp.float32, remat=False,
                                   scan_layers=False)
    tcfg = TrainStepConfig(lr=args.lr)
    state = make_train_state(jax.random.PRNGKey(0), cfg, lite, tcfg)
    step = jax.jit(make_hapfl_train_step(cfg, lite, tcfg), donate_argnums=0)

    t0 = time.time()
    for i, batch in enumerate(token_batches(cfg, args.batch, args.seq,
                                            args.steps)):
        state, metrics = step(state, batch)
        if i % max(args.steps // 10, 1) == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss={float(metrics['loss']):.4f} "
                  f"ce_local={float(metrics['ce_local']):.4f} "
                  f"ce_lite={float(metrics['ce_lite']):.4f} "
                  f"({time.time() - t0:.1f}s)")
    if args.checkpoint:
        save_checkpoint(args.checkpoint, state["params"], step=args.steps)
        print("saved", args.checkpoint)


if __name__ == "__main__":
    main()
