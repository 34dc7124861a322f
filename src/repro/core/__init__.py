"""HAPFL core — the paper's contribution.

  ppo          — PPO actor/critic (shared by both agents)
  allocation   — PPO1: heterogeneous model allocation
  intensity    — PPO2: training-intensity adjustment
  distill      — KD-based mutual learning (LiteModel <-> local model)
  aggregation  — entropy + accuracy weighted aggregation
  nested       — cross-size nested (HeteroFL-style) aggregation
  latency      — client performance / straggling-latency model
"""
from repro.core.ppo import PPOAgent, PPOConfig, discounted_returns
from repro.core.allocation import ModelAllocator
from repro.core.intensity import IntensityAllocator
from repro.core.distill import (mutual_losses, make_mutual_train_step,
                                make_single_train_step, LAMBDAS)
from repro.core.aggregation import (information_entropy, aggregation_weights,
                                    weighted_aggregate, fedavg_aggregate,
                                    group_aggregate)
from repro.core.nested import (extract_submodel, embed_submodel,
                               coverage_mask, nested_aggregate)
from repro.core.latency import LatencyModel, straggling_latency
from repro.core.population import ClientStore
