"""Quickstart: the full HAPFL loop on a small simulated FL fleet.

Runs in ~2 minutes on CPU:
  1. builds a 10-client heterogeneous environment (synthetic MNIST-like data,
     Dirichlet non-IID, 10x speed disparity),
  2. warms the two PPO agents on the latency model,
  3. runs federated rounds with real mutual-KD CNN training,
  4. prints straggling latency + accuracy progress.

  PYTHONPATH=src python examples/quickstart.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np

from repro.fl import FLEnvironment, FLSimConfig, HAPFLServer
from repro.launch.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    cfg = FLSimConfig(dataset="mnist", n_train=1500, n_test=300,
                      default_epochs=8, batches_per_epoch=2, lr=1e-2)
    env = FLEnvironment(cfg)
    print(f"clients: {cfg.n_clients}, per-round: {cfg.k_per_round}, "
          f"speeds: {[round(float(v), 1) for v in env.store.base_speed]}")
    srv = HAPFLServer(env, seed=0)   # engine="auto" picks per regime
    print(f"training engine: {srv.engine}")

    print("\n== RL warmup (latency-only, 800 rounds) ==")
    hist = srv.pretrain_rl(800)
    early = np.mean([h["straggling"] for h in hist[:100]])
    late = np.mean([h["straggling"] for h in hist[-100:]])
    print(f"straggling latency: {early:.1f} -> {late:.1f} "
          f"({100 * (1 - late / early):.1f}% reduction)")

    print("\n== federated training (8 rounds, real mutual-KD training) ==")
    for r in srv.run(8, verbose=True):
        pass
    s = srv.summary()
    print("\nsummary:", {k: round(v, 4) for k, v in s.items()})


if __name__ == "__main__":
    main()
