"""Population-scale client state (DESIGN.md §15): the fleet and latency
pin, vectorized event machinery, sampled participation, memory shape,
and 10k-client scale regressions."""
import numpy as np
import pytest

from repro.core.latency import AvailabilityModel
from repro.core.population import ClientStore
from repro.fl import (FLEnvironment, FLSimConfig, HAPFLServer,
                      PopulationEnv)
from repro.service import ParamService, synth_update
from repro.sim import (BufferedPolicy, Event, EventQueue, EventScheduler,
                       SyncPolicy)
from repro.sim.events import ARRIVAL, ASSESS_DONE, DEADLINE, DROPOUT

CFG = FLSimConfig(dataset="mnist", n_train=300, n_test=80, n_clients=10,
                  k_per_round=4, batches_per_epoch=1, default_epochs=2,
                  batch_size=16, seed=3)


# --------------------------------------------------------------------- #
# fleet + latency pin: the hex literals were recorded from FLEnvironment
# built over per-client profile objects, before the ClientStore became
# the only layout — every number must survive bitwise
# --------------------------------------------------------------------- #
_PIN_BASE_SPEED = (
    "0x1.4000000000000p+3", "0x1.290fca9c761f7p+2",
    "0x1.0000000000000p+0", "0x1.ab08a305b6db4p+0",
    "0x1.4aa2ffb466100p+0", "0x1.642ae80fd415ap+1",
    "0x1.7fab7fe3f7a26p+2", "0x1.cc02160cfaba7p+1",
    "0x1.13c484138704fp+1", "0x1.ef875c9cfd6a6p+2",
)
_PIN_DATASET_SIZE = (18, 21, 24, 31, 56, 24, 52, 34, 24, 16)
_PIN_ASSESS = {
    0: (
        "0x1.7b1fa701c3ae2p-4", "0x1.9d15c761d766fp-3",
        "0x1.138402db3beb1p+0", "0x1.f0106ae535bc4p-1",
        "0x1.57299ee16b6a5p+1", "0x1.203bf03ded139p-1",
        "0x1.f799b51093d39p-2", "0x1.888b20e6402c9p-2",
        "0x1.e7a7b9f484034p-2", "0x1.a947b92c10956p-4",
    ),
    7: (
        "0x1.4122c6c7263c0p-4", "0x1.963d486635e6bp-3",
        "0x1.2fe52f66c66bdp+0", "0x1.3a23426b3c739p+0",
        "0x1.5f2135acd0f23p+1", "0x1.e22adea703bcbp-2",
        "0x1.8338fc3fa085fp-2", "0x1.9209cbbc540aap-2",
        "0x1.01624b8652f5fp-1", "0x1.fe7a48b5c1de1p-4",
    ),
    31: (
        "0x1.e314e3121bf24p-4", "0x1.34df82fdc9d0bp-2",
        "0x1.4c639e01c21bbp+0", "0x1.9b47a8eacbf7cp-1",
        "0x1.d697648b9a816p+0", "0x1.78492bb820cb9p-2",
        "0x1.f21959ded26a2p-2", "0x1.118658faf4803p-1",
        "0x1.39bc3daef4720p-1", "0x1.783abf2deba08p-4",
    ),
}
_PIN_LOCAL = {
    0: (
        "0x1.1188e940e230dp-1", "0x1.3f428d6589beep+0",
        "0x1.2a2c75cf712aap+4", "0x1.7f644b5fa4715p+3",
        "0x1.357c897b216fap+6", "0x1.bd88940ab70adp+0",
        "0x1.6b5806b0bf03bp+2", "0x1.c713783f030a8p+1",
        "0x1.5fd6e87af370dp+3", "0x1.9adb406ca8636p+0",
    ),
    7: (
        "0x1.cf64e4b3e5d7ap-2", "0x1.39f81af876287p+0",
        "0x1.48e31c87948a0p+4", "0x1.e592d765bdad0p+3",
        "0x1.3cabf8cf0df72p+6", "0x1.74a6c8265be00p+0",
        "0x1.1760ce16dab93p+2", "0x1.d215584986926p+1",
        "0x1.7366b02cbf7a1p+3", "0x1.ed2a16f8163f5p+0",
    ),
    31: (
        "0x1.5c8a280d84ad5p-1", "0x1.dd6f94b4e8184p+0",
        "0x1.67b96d47e17b0p+4", "0x1.3ddd65486acadp+3",
        "0x1.a868fc6b341b2p+5", "0x1.22d1abfec51e7p+0",
        "0x1.675fe86b0e6a6p+2", "0x1.3d18faf5c77b0p+2",
        "0x1.c4b71b8a1cc74p+3", "0x1.6b7827b08772ep+0",
    ),
}


def test_fleet_and_latency_pinned_bitwise():
    env = FLEnvironment(CFG)
    store, lat = env.store, env.latency
    clients = list(range(CFG.n_clients))
    sizes = ["small" if c % 2 else "large" for c in clients]
    taus = [1 + (c % 5) for c in clients]

    def hexes(a):
        return tuple(float(x).hex() for x in a)

    assert hexes(store.base_speed) == _PIN_BASE_SPEED
    assert tuple(store.dataset_size.tolist()) == _PIN_DATASET_SIZE
    for r in (0, 7, 31):
        assert hexes(lat.assessment_times(store, clients, r)) \
            == _PIN_ASSESS[r]
        assert hexes(lat.local_train_times(store, clients, r, sizes,
                                           taus)) == _PIN_LOCAL[r]


def test_synthetic_speeds_match_for_partition():
    """Both constructors draw base speeds the same way, so a synthetic
    population has the speed layout of a real partition of equal
    (n, ratio, seed)."""
    for n, ratio, seed in ((10, 10.0, 3), (257, 50.0, 0), (1000, 20.0, 11)):
        syn = ClientStore.synthetic(n, ratio, seed=seed)
        part = ClientStore.for_partition(n, ratio, np.full(n, 30),
                                         np.ones(n), seed=seed)
        assert syn.base_speed.tobytes() == part.base_speed.tobytes()


def test_store_ef_is_shared_with_server():
    srv = HAPFLServer(FLEnvironment(CFG), seed=3, codec="int8")
    assert srv._ef is srv.store.ef
    recs = srv.run(1)
    assert len(srv.store.ef) > 0        # lossy codec left residuals behind
    assert srv.store.nbytes() > 0
    # the store recorded what was planned
    planned = sorted({c for r in recs for c in r.clients})
    assert sorted(np.flatnonzero(srv.store.n_planned > 0).tolist()) == planned


# --------------------------------------------------------------------- #
# event queue at scale: canonical order, batch == sequential
# --------------------------------------------------------------------- #
def _random_events(n, seed):
    rng = np.random.default_rng(seed)
    kinds = [ASSESS_DONE, ARRIVAL, DEADLINE, DROPOUT]
    # coarse times force plenty of exact ties across kinds/clients
    return [Event(float(rng.integers(0, n // 10)),
                  kinds[int(rng.integers(4))],
                  int(rng.integers(n)), int(rng.integers(8)))
            for _ in range(n)]


def test_event_queue_10k_pop_order_insertion_invariant():
    evs = _random_events(10_000, seed=0)
    q1, q2, q3 = EventQueue(), EventQueue(), EventQueue()
    for ev in evs:
        q1.push(ev)
    q2.push_batch(evs)                       # heapify path (big batch)
    rng = np.random.default_rng(1)
    perm = rng.permutation(len(evs))
    for j in perm:                           # same set, shuffled pushes
        q3.push(evs[int(j)])
    out1 = [q1.pop() for _ in range(len(evs))]
    assert out1 == [q2.pop() for _ in range(len(evs))]
    assert out1 == [q3.pop() for _ in range(len(evs))]
    keys = [ev.sort_key() for ev in out1]
    assert keys == sorted(keys)


def test_push_batch_small_batches_match_sequential():
    evs = _random_events(64, seed=2)
    q1, q2 = EventQueue(), EventQueue()
    for ev in evs[:5]:
        q1.push(ev)
        q2.push(ev)
    q2.push_batch(evs[5:12])                 # small batch: heappush path
    q2.push_batch(evs[12:])                  # large batch: heapify path
    for ev in evs[5:]:
        q1.push(ev)
    while q1:
        assert q1.pop() == q2.pop()
    assert not q2


# --------------------------------------------------------------------- #
# availability at scale: purity + bounded trace cache
# --------------------------------------------------------------------- #
def test_availability_10k_query_order_pure_and_bounded():
    n = 10_000
    rng = np.random.default_rng(5)
    clients = rng.integers(0, n, size=3000)
    times = rng.uniform(0.0, 500.0, size=3000)
    bounded = AvailabilityModel(n, seed=9, max_cached=64)
    reference = AvailabilityModel(n, seed=9)         # default large cache
    got = [bounded.available(int(c), float(t))
           for c, t in zip(clients, times)]
    # reference queried in REVERSE order: purity + eviction regeneration
    want = [reference.available(int(c), float(t))
            for c, t in zip(clients[::-1], times[::-1])][::-1]
    assert got == want
    assert bounded.cached_traces <= 64
    assert bounded.n_evicted > 0
    # re-querying an evicted client regenerates its trace bit-identically
    c0, t0 = int(clients[0]), float(times[0])
    assert bounded.available(c0, t0) == got[0]
    assert bounded.next_online(c0, t0) == reference.next_online(c0, t0)


# --------------------------------------------------------------------- #
# sampled participation
# --------------------------------------------------------------------- #
class _EvenOnly:
    """Stub availability: odd clients are always offline."""

    def available(self, c, t):
        return c % 2 == 0


def test_sample_available_excludes_inflight_and_offline():
    store = ClientStore.synthetic(1000, 10.0, seed=1)
    store.open_slots([0, 2, 4, 6], wave=0, indices=[0, 1, 2, 3], version=0)
    rng = np.random.default_rng(0)
    picked = store.sample_available(32, rng, 0.0, _EvenOnly())
    assert len(picked) == 32
    assert picked == sorted(picked) and len(set(picked)) == 32
    assert all(c % 2 == 0 for c in picked)
    assert not any(c in (0, 2, 4, 6) for c in picked)


def test_sample_available_exact_fallback_when_pool_is_tight():
    store = ClientStore.synthetic(10, 10.0, seed=1)
    store.open_slots([1, 3, 5, 7, 9], 0, list(range(5)), 0)
    rng = np.random.default_rng(0)
    # k exceeds the dispatchable pool: rejection sampling alone can't fill
    # it, the exact fallback must return everyone who is eligible
    assert store.sample_available(8, rng, 0.0) == [0, 2, 4, 6, 8]


def test_slot_bookkeeping_counts_outcomes():
    store = ClientStore.synthetic(6, 4.0, seed=0)
    store.open_slots([1, 4], 3, [0, 1], 7, deadline=10.0)
    assert store.candidates().tolist() == [0, 2, 3, 5]
    assert store.expired_clients(9.0).size == 0
    assert store.expired_clients(11.0).tolist() == [1, 4]
    store.close_slot(1, "update")
    store.close_slot(4, "expired")
    assert not store.inflight.any()
    assert store.n_updates[1] == 1 and store.n_expired[4] == 1
    assert store.ticket_deadline[1] == np.inf


def test_expired_order_matches_legacy_deadline_then_client():
    store = ClientStore.synthetic(8, 4.0, seed=0)
    store.open_slots([5, 2, 7, 1], 0, list(range(4)), 0,
                     deadline=np.array([3.0, 9.0, 3.0, 5.0]))
    # legacy poll() sorts by (deadline, client): 3.0->{5,7}, 5.0->1
    assert store.expired_clients(6.0).tolist() == [5, 7, 1]


# --------------------------------------------------------------------- #
# population environment: 10k-client scheduler smoke + determinism
# --------------------------------------------------------------------- #
def _pop_sched(n=10_000, seed=0, participation="sampled"):
    cfg = FLSimConfig(dataset="mnist", n_clients=n, k_per_round=16,
                      default_epochs=2, seed=seed)
    env = PopulationEnv(cfg)
    srv = HAPFLServer(env, seed=seed, engine="sequential")
    sched = EventScheduler(
        srv, BufferedPolicy(buffer_m=8),
        availability=AvailabilityModel(n, seed=seed + 1, max_cached=512),
        latency_only=True, eval_accuracy=False,
        participation=participation)
    return sched


def test_population_env_10k_smoke_and_determinism():
    res1 = _pop_sched(seed=4).run(waves=20)
    res2 = _pop_sched(seed=4).run(waves=20)
    assert res1.n_updates > 0 and res1.n_events > 0
    assert res1.summary() == res2.summary()
    assert [(r.time, r.version, r.n_updates, r.staleness)
            for r in res1.records] == \
           [(r.time, r.version, r.n_updates, r.staleness)
            for r in res2.records]


def test_population_sampled_never_double_dispatches():
    sched = _pop_sched(n=2000, seed=6)
    sched.run(waves=12)
    st = sched.store
    # in-flight mask mirrors the scheduler dict exactly
    assert set(np.flatnonzero(st.inflight).tolist()) == \
        set(sched.inflight.keys())
    # every update/expiry was accounted once
    assert int(st.n_updates.sum()) == sched.n_updates


# --------------------------------------------------------------------- #
# memory shape: inactive clients materialize no parameter pytrees
# --------------------------------------------------------------------- #
def test_population_run_materializes_no_client_params():
    sched = _pop_sched(n=5000, seed=1)
    sched.run(waves=10)
    for info in sched._waves.values():
        assert info["plan"].client_params == []
    # dense store stays a few hundred bytes per client, EF empty
    st = sched.store
    assert st.ef == {}
    assert st.nbytes() < 250 * st.n_clients


def test_service_tickets_pin_globals_by_reference():
    cfg = FLSimConfig(dataset="mnist", n_train=200, n_test=60, n_clients=6,
                      k_per_round=3, batches_per_epoch=1, default_epochs=2,
                      batch_size=16)
    srv = HAPFLServer(FLEnvironment(cfg), seed=0)
    svc = ParamService(srv, policy="async", min_deadline=50.0)
    tks = svc.dispatch([0, 1], now=0.0)
    for tk in tks:
        assert tk.ref_lite is srv.lite_params           # reference, no copy
        assert tk.ref_local is srv.global_by_size[tk.size]
    # store slots mirror the ticket dict, deadlines included
    st = svc.store
    assert set(np.flatnonzero(st.inflight).tolist()) == set(svc.tickets)
    for tk in tks:
        assert st.ticket_deadline[tk.client] == tk.deadline
    svc.submit(0, synth_update(tks[0], seed=1), now=1.0)
    assert set(np.flatnonzero(st.inflight).tolist()) == set(svc.tickets)
    assert st.n_updates[0] == 1
    # expiry path closes the slot and marks churn
    svc.poll(now=1e9)
    assert not st.inflight.any()
    assert bool(st.churned[1])
    assert svc._churned_clients() == [1]
