"""Event-driven simulator: sync parity, staleness weights, edge cases,
event-ordering determinism, latency purity, comm/availability models."""
import itertools

import numpy as np
import pytest

from repro.core.aggregation import (aggregation_weights, staleness_discount,
                                    staleness_weights, weighted_aggregate)
from repro.core.latency import (AvailabilityModel, make_comm_model,
                                straggling_latency)
from repro.fl import FLEnvironment, FLSimConfig, HAPFLServer
from repro.sim import (ARRIVAL, DEADLINE, DROPOUT, AsyncPolicy,
                       BufferedPolicy, DeadlinePolicy, Event, EventQueue,
                       EventScheduler, SyncPolicy, make_policy)

CFG = FLSimConfig(dataset="mnist", n_train=300, n_test=80, n_clients=8,
                  k_per_round=4, batches_per_epoch=1, default_epochs=2,
                  batch_size=16)


def fresh_server(seed=3, **kw):
    kw.setdefault("use_ppo1", True)
    kw.setdefault("use_ppo2", True)
    return HAPFLServer(FLEnvironment(CFG), seed=seed, **kw)


# --------------------------------------------------------------------- #
# sync-policy parity: the scheduler must reproduce HAPFLServer.run
# --------------------------------------------------------------------- #
def test_sync_policy_reproduces_server_run_exactly():
    srv_a = fresh_server()
    recs_a = srv_a.run(3)
    srv_b = fresh_server()
    res = EventScheduler(srv_b, SyncPolicy()).run(waves=3)
    recs_b = srv_b.history
    assert len(recs_b) == 3
    for a, b in zip(recs_a, recs_b):
        assert a.clients == b.clients
        assert a.sizes == b.sizes
        assert a.intensities == b.intensities
        assert a.assess_times == b.assess_times
        assert a.local_times == b.local_times
        assert a.straggling == b.straggling
        assert a.wall_time == b.wall_time
        assert a.reward_ppo1 == b.reward_ppo1
        assert a.reward_ppo2 == b.reward_ppo2
        assert a.acc_lite == b.acc_lite
        assert a.acc_by_size == b.acc_by_size
        assert a.client_acc == b.client_acc
    # the virtual clock advanced by exactly the sum of barrier rounds
    assert res.sim_time == pytest.approx(sum(r.wall_time for r in recs_a))


def test_latency_draws_are_query_order_independent():
    """Prerequisite for parity: jitter is a pure function of
    (client, round), so asking in any order/multiplicity matches."""
    env = FLEnvironment(CFG)
    lat, store = env.latency, env.store
    v1 = lat.local_train_times(store, [2], 7, ["small"], [3])[0]
    a1 = lat.assessment_times(store, [2], 7)[0]
    everyone = list(range(CFG.n_clients))
    for q, r in itertools.product(everyone, range(5)):
        lat.assessment_times(store, [q], r)
        lat.local_train_times(store, [q], r, ["large"], [2])
    assert lat.local_train_times(store, [2], 7, ["small"], [3])[0] == v1
    assert lat.assessment_times(store, [2], 7)[0] == a1
    # inside a cohort, in any order, the client's draw is the same
    cohort = everyone[::-1]
    i = cohort.index(2)
    assert lat.assessment_times(store, cohort, 7)[i] == a1
    assert lat.local_train_times(store, cohort, 7, ["small"] * len(cohort),
                                 [3] * len(cohort))[i] == v1


# --------------------------------------------------------------------- #
# staleness weighting
# --------------------------------------------------------------------- #
def test_staleness_discount_monotone():
    d = staleness_discount([0, 1, 2, 5, 10], exponent=0.5)
    assert d[0] == 1.0
    assert np.all(np.diff(d) < 0)
    # stronger exponent discounts harder
    assert staleness_discount([4], 1.0)[0] < staleness_discount([4], 0.5)[0]


def test_staleness_weights_none_is_legacy_eq38():
    e, a = [1.0, 2.0, 0.5], [0.3, 0.6, 0.2]
    assert np.array_equal(staleness_weights(e, a, None),
                          aggregation_weights(e, a))


def test_staleness_weights_normalized_and_penalize_stale():
    e, a = [1.0, 1.0, 1.0], [0.5, 0.5, 0.5]
    w = staleness_weights(e, a, [0, 3, 0])
    assert w.sum() == pytest.approx(1.0)
    assert w[1] < w[0] == pytest.approx(w[2])


def test_weighted_aggregate_mix_rate():
    g = {"w": np.ones(3, np.float32)}
    c = [{"w": np.full(3, 5.0, np.float32)}]
    out0 = weighted_aggregate(g, c, [1.0], mix=0.0)
    out1 = weighted_aggregate(g, c, [1.0], mix=1.0)
    outh = weighted_aggregate(g, c, [1.0], mix=0.5)
    assert np.allclose(np.asarray(out0["w"]), 1.0)   # untouched
    assert np.allclose(np.asarray(out1["w"]), 5.0)   # full replacement
    assert np.allclose(np.asarray(outh["w"]), 3.0)   # halfway


def test_buffered_records_staleness():
    srv = fresh_server(use_ppo1=False, use_ppo2=False)
    res = EventScheduler(srv, BufferedPolicy(buffer_m=2),
                         latency_only=True).run(waves=None, max_updates=24)
    stal = [s for r in res.records for s in r.staleness]
    assert all(s >= 0 for s in stal)
    # a 10x-heterogeneous fleet must produce genuinely stale updates
    assert max(stal) > 0


# --------------------------------------------------------------------- #
# dropout / empty-cohort edge cases
# --------------------------------------------------------------------- #
def test_straggling_latency_small_sets():
    assert straggling_latency([]) == 0.0
    assert straggling_latency([4.2]) == 0.0
    assert straggling_latency([1.0, 4.0]) == 3.0


def test_deadline_nobody_finishes():
    srv = fresh_server(use_ppo1=False, use_ppo2=False)
    res = EventScheduler(srv, DeadlinePolicy(fixed=1e-9),
                         latency_only=True).run(waves=3)
    assert res.n_updates == 0
    assert res.n_waves == 3                    # sim keeps going regardless
    assert res.n_dropped == 3 * CFG.k_per_round
    assert all(r.n_updates == 0 and r.straggling == 0.0 for r in res.records)


def test_deadline_drops_stragglers_and_beats_sync_time():
    srv = fresh_server(use_ppo1=False, use_ppo2=False)
    sync = EventScheduler(srv, SyncPolicy(), latency_only=True)
    r_sync = sync.run(waves=None, max_updates=32)
    srv2 = fresh_server(use_ppo1=False, use_ppo2=False)
    dead = EventScheduler(srv2, DeadlinePolicy(quantile=0.5),
                          latency_only=True)
    r_dead = dead.run(waves=None, max_updates=32)
    assert r_dead.n_dropped > 0
    # aggregating at the median predicted finish cuts per-update sim time
    assert (r_dead.sim_time / max(r_dead.n_updates, 1)
            < r_sync.sim_time / r_sync.n_updates)


def test_availability_dropouts_and_rejoin():
    srv = fresh_server(use_ppo1=False, use_ppo2=False)
    av = AvailabilityModel(CFG.n_clients, mean_on=30.0, mean_off=20.0, seed=1)
    res = EventScheduler(srv, BufferedPolicy(buffer_m=2), availability=av,
                         latency_only=True).run(waves=None, max_updates=20)
    assert res.n_updates == 20                 # sim survived the churn
    assert res.n_dropped > 0


def test_availability_trace_pure_and_consistent():
    av1 = AvailabilityModel(4, mean_on=10.0, mean_off=5.0, seed=7)
    av2 = AvailabilityModel(4, mean_on=10.0, mean_off=5.0, seed=7)
    probes = [0.0, 3.0, 11.0, 40.0, 7.5, 100.0]     # deliberately unsorted
    a = [av1.available(c, t) for c in range(4) for t in probes]
    b = [av2.available(c, t) for c in range(4) for t in reversed(probes)]
    assert a == [av2.available(c, t) for c in range(4) for t in probes]
    for c in range(4):
        t_on = av1.next_online(c, 12.0)
        assert t_on >= 12.0 and av1.available(c, t_on)
        off = av1.next_offline(c, 0.0, 1000.0)
        assert off is None or not av1.available(c, off + 1e-9)


def test_comm_model_scales_with_bytes_and_bandwidth():
    comm = make_comm_model({"small": 1e4, "large": 1e5}, 5e3, 4, seed=0)
    for c in range(4):
        assert comm.upload_time(c, "large") > comm.upload_time(c, "small")
        # downlinks are faster than uplinks
        assert comm.download_time(c, "small") < comm.upload_time(c, "small")
    lone = comm.upload_time(1, "small", include_lite=False)
    assert comm.upload_time(1, "small") > lone


# --------------------------------------------------------------------- #
# event-ordering determinism
# --------------------------------------------------------------------- #
def test_event_queue_pop_order_invariant_to_push_order():
    events = [Event(2.0, ARRIVAL, 3, 0), Event(2.0, ARRIVAL, 1, 0),
              Event(2.0, DEADLINE, -1, 0), Event(1.5, DROPOUT, 2, 0),
              Event(2.0, DROPOUT, 1, 0), Event(3.0, ARRIVAL, 0, 1)]
    orders = []
    for perm in itertools.permutations(events):
        q = EventQueue()
        for ev in perm:
            q.push(ev)
        orders.append([q.pop() for _ in range(len(events))])
    assert all(o == orders[0] for o in orders)
    # arrivals at the deadline instant still count; dropouts lose ties
    kinds = [(e.time, e.kind) for e in orders[0]]
    assert kinds.index((2.0, ARRIVAL)) < kinds.index((2.0, DEADLINE))
    assert kinds.index((2.0, DEADLINE)) < kinds.index((2.0, DROPOUT))


def test_async_policy_applies_every_arrival():
    srv = fresh_server(use_ppo1=False, use_ppo2=False)
    res = EventScheduler(srv, AsyncPolicy(), latency_only=True).run(
        waves=None, max_updates=12)
    applied = [r for r in res.records if r.n_updates > 0]
    assert all(r.n_updates == 1 for r in applied)
    assert res.mean_straggling == 0.0          # singleton sets have no spread


def test_make_policy_factory():
    assert make_policy("deadline", quantile=0.8).quantile == 0.8
    with pytest.raises(ValueError):
        make_policy("nope")


# --------------------------------------------------------------------- #
# chunked full-set evaluation (no more first-max_n truncation)
# --------------------------------------------------------------------- #
def test_test_accuracy_covers_full_set_in_chunks():
    env = FLEnvironment(CFG)
    srv = HAPFLServer(env, seed=0)
    params, ccfg = srv.lite_params, env.lite_cfg
    full = env.test_accuracy(params, ccfg, chunk=1000)   # single-shot truth
    assert env.test_accuracy(params, ccfg, chunk=32) == pytest.approx(full)
    assert env.test_accuracy(params, ccfg, chunk=79) == pytest.approx(full)
    c = 1
    part = env.client_test_accuracy(params, ccfg, c, chunk=7)
    assert part == pytest.approx(
        env.client_test_accuracy(params, ccfg, c, chunk=10 ** 6))
