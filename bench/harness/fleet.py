"""Builds the system under test from a config, a traffic mix and a seed,
and watches it through instance wrappers (the program stays untouched).

The fleet (data pool, Dirichlet partition, client speeds) and the PPO
agents come from the traffic's `fleet_seed` where it gives one, so every
seed trains the same cohorts at the same sizes and intensities; the run's
seed then draws each client's batches and the initial global weights.
Without a `fleet_seed` the run's seed draws the fleet and the agents too.
"""
from __future__ import annotations

import copy
import functools
import resource
import sys
import threading
import time
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

import jax
import numpy as np

from bench.harness import spec


_T0 = time.perf_counter()


def note(msg: str) -> None:
    """A progress line on stderr, stamped with seconds since import and the
    process's peak resident memory."""
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20
    print(f"[bench {time.perf_counter() - _T0:7.1f}s peak {peak:5.1f}GiB] "
          f"{msg}", file=sys.stderr, flush=True)


def host_gib() -> float:
    """The process's resident memory now less its file-backed pages where
    the kernel counts them apart, in GiB (/proc/self/statm; 0 where there
    is none)."""
    try:
        with open("/proc/self/statm") as f:
            _, resident, shared = (int(v) for v in f.read().split()[:3])
    except (OSError, ValueError):
        return 0.0
    return (resident - shared) * resource.getpagesize() / 2 ** 30


class HostMemory:
    """`host_gib()` when the block starts (`start`) and the highest reading
    while it runs (`peak`), sampled every `every` seconds on a thread of
    its own, which ends with the block."""

    def __init__(self, every: float = 0.05):
        self.every = every
        self.start = self.peak = 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._sample, daemon=True)

    def _sample(self) -> None:
        while not self._stop.wait(self.every):
            self.peak = max(self.peak, host_gib())

    def __enter__(self) -> "HostMemory":
        self.start = self.peak = host_gib()
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()
        self.peak = max(self.peak, host_gib())


def derive(seed: int, stream: int) -> int:
    """A 31-bit seed of its own for each use of the run's seed."""
    return int(np.random.SeedSequence([seed, stream])
               .generate_state(1)[0] & 0x7FFFFFFF)


def build(config: Dict, traffic: Dict, seed: int, shadow: bool = True):
    """(env, server, shadow_env, server seed) for one run. `shadow_env` is
    an untouched copy of the environment for look-ahead planning (None
    without `shadow`)."""
    from repro.fl import FLEnvironment, HAPFLServer
    fam = spec.family(config)
    fam.check_config(config)
    fleet = traffic.get("fleet_seed")
    fleet_seed = derive(seed, 0) if fleet is None else int(fleet)
    server_seed = derive(seed, 1) if fleet is None else int(fleet)
    env = FLEnvironment(fam.sim_config(config, traffic, fleet_seed))
    fam.check_data(config, env.data)
    shadow_env = copy.deepcopy(env) if shadow else None
    srv = HAPFLServer(env, seed=server_seed)
    if srv.engine != "batched":
        raise spec.SpecError(f"engine resolved to {srv.engine!r}, the cell "
                             "measures the batched cohort engine")
    return env, srv, shadow_env, server_seed


def seeded_weights(config: Dict, seed: int) -> Dict:
    """The LiteModel and every size's global model, drawn from the seed on
    the device in one jitted call."""
    names = ["lite"] + list(config["size_names"])
    fam = spec.family(config)

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(names))
        return {n: fam.init_params(k, config, n)
                for n, k in zip(names, keys)}
    key = np.random.SeedSequence([seed, 2]).generate_state(2, np.uint32)
    return init(jax.numpy.asarray(key))


def reseed(env, srv, config: Dict, seed: int) -> Dict:
    """Draw the batches and the initial globals from the run's seed.
    Returns the initial weights as host arrays."""
    for i, loader in enumerate(env.loaders):
        loader.rng = np.random.default_rng([seed, 3, i])
    w = seeded_weights(config, seed)
    if jax.tree_util.tree_structure(w["lite"]) != \
            jax.tree_util.tree_structure(srv.lite_params):
        raise spec.SpecError("the server's parameter layout differs from "
                             "the reference's")
    srv.lite_params = w["lite"]
    srv.global_by_size = {s: w[s] for s in config["size_names"]}
    return jax.device_get(w)


# ---------------------------------------------------------------------- #
# look-ahead: the shapes the window will reach
# ---------------------------------------------------------------------- #
def plan_ahead(shadow_env, server_seed: int, rounds: int) -> List[Tuple]:
    """(clients, sizes, intensities) of the next `rounds` rounds of a server
    started like the real one. PPO's decisions depend only on the latency
    model and the agents, never on the trained weights, so a latency-only
    twin makes the same decisions round for round."""
    from repro.fl import HAPFLServer
    twin = HAPFLServer(shadow_env, seed=server_seed, engine="sequential")
    out = []
    for _ in range(rounds):
        rec = twin.run_round(latency_only=True)
        out.append((rec.clients, rec.sizes, rec.intensities))
    return out


def next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def cohort_groups(plans: Sequence[Tuple], bpe: int, batch_of) -> Dict:
    """One example group for every cohort shape the plans reach, keyed by
    (size, batch, padded clients, padded steps): clients of one size and
    batch whose steps share a power-of-two ceiling train together, the
    client axis padded to a power of two (at least 4)."""
    shapes: Dict[Tuple, Tuple] = {}
    for clients, sizes, taus in plans:
        groups = defaultdict(list)
        for c, s, t in zip(clients, sizes, taus):
            groups[(s, batch_of(c), next_pow2(int(t) * bpe))].append((c, t))
        for (s, b, steps), members in groups.items():
            key = (s, b, max(next_pow2(len(members)), 4), steps)
            shapes.setdefault(key, tuple(members))
    return dict(sorted(shapes.items()))


def warm_cohorts(srv, groups: Dict) -> None:
    """Compile (or load) and run once every cohort shape, through the
    server's own engine."""
    for (size, _, _, _), members in groups.items():
        clients = [c for c, _ in members]
        srv.batched_engine.train_cohort(
            clients, [size] * len(clients), [t for _, t in members],
            srv.global_by_size, srv.lite_params)


def warm_eval(env, srv, config: Dict) -> None:
    """Every evaluation shape: each client's own set and the test set, for
    the LiteModel and every size."""
    cfgs = [(srv.lite_params, env.lite_cfg)] + [
        (srv.global_by_size[s], env.pool[s]) for s in config["size_names"]]
    for params, cfg in cfgs:
        env.test_accuracy(params, cfg)
        for c in range(env.cfg.n_clients):
            env.client_test_accuracy(params, cfg, c)


def warm_aggregation(srv) -> None:
    """The eager ops of the Eq. 39 fold, for every model's leaf shapes."""
    from repro.core.aggregation import weighted_aggregate
    for params in [srv.lite_params, *srv.global_by_size.values()]:
        jax.block_until_ready(weighted_aggregate(
            params, [jax.device_get(params)], [1.0]))


# ---------------------------------------------------------------------- #
# wrappers and spans
# ---------------------------------------------------------------------- #
def span_totals(tracer) -> Dict[str, float]:
    """Seconds per name of the program's wall-clock spans (none without a
    tracer)."""
    out: Dict[str, float] = defaultdict(float)
    for ev in (tracer.events if tracer is not None else ()):
        if ev.get("ph") == "X" and ev.get("clock") == "wall":
            out[ev["name"]] += ev.get("dur", 0.0)
    return dict(out)

class Probe:
    """Instance wrappers that time a call, keep its interval on the host
    clock while `recording`, and hand its arguments and result to an
    optional hook."""

    def __init__(self):
        self.seconds = defaultdict(float)
        self.calls = defaultdict(int)
        self.hooks: Dict[str, callable] = {}
        self.recording = False
        self.intervals: List[Tuple[str, float, float]] = []

    def wrap(self, obj, attr: str, label: str) -> None:
        fn = getattr(obj, attr)

        @functools.wraps(fn)
        def wrapped(*args, **kw):
            t0 = time.perf_counter()
            out = fn(*args, **kw)
            t1 = time.perf_counter()
            self.seconds[label] += t1 - t0
            self.calls[label] += 1
            if self.recording:
                self.intervals.append((label, t0, t1))
            hook = self.hooks.get(label)
            if hook is not None:
                hook(args, kw, out)
            return out
        setattr(obj, attr, wrapped)

    def mark(self, name: str, t0: float, t1: float) -> None:
        if self.recording:
            self.intervals.append((name, t0, t1))

    def reset(self) -> None:
        self.seconds.clear()
        self.calls.clear()
        self.intervals.clear()


def watch_training(probe: Probe, env, srv) -> None:
    probe.wrap(env, "prefetch_round", "bench.prefetch")
    probe.wrap(srv.batched_engine, "train_cohort", "bench.cohort")
    # the engine's own name of each group dispatch (size, padded clients,
    # padded steps), kept as a zero-length interval
    probe.wrap(srv.batched_engine, "_group_label", "bench.group_label")
    probe.hooks["bench.group_label"] = lambda a, kw, out: probe.mark(
        out, time.perf_counter(), time.perf_counter())
    probe.wrap(env, "client_test_accuracy", "bench.eval")
    probe.wrap(env, "test_accuracy", "bench.eval")
    watch_server(probe, srv)


def watch_server(probe: Probe, srv) -> None:
    """Intervals only, so that idle gaps on the device can be put down to
    what the host was doing."""
    for attr in ("plan_wave", "apply_updates", "feedback_wave",
                 "record_wave"):
        probe.wrap(srv, attr, f"bench.{attr}")
