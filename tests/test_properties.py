"""Property-based tests for the system's invariants.

Runs under real hypothesis when installed (CI does); otherwise falls
back to the deterministic shim in repro.utils.proptest so the properties
still execute — instead of skipping — in the pinned container.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - exercised in the pinned container
    from repro.utils.proptest import given, settings
    from repro.utils import proptest as st

from repro.comm.codec import make_codec
from repro.obs.registry import (Histogram, IntHistogram, Reservoir,
                                latency_stats)
from repro.comm.quantize import dequantize, quantize
from repro.core.aggregation import (aggregation_weights, fedavg_aggregate,
                                    information_entropy, staleness_weights,
                                    weighted_aggregate)
from repro.core.latency import LatencyModel
from repro.core.population import ClientStore
from repro.core.ppo import discounted_returns
from repro.launch.hlo_analysis import shape_bytes

floats = st.floats(min_value=0.01, max_value=100.0, allow_nan=False)


@given(st.lists(floats, min_size=2, max_size=16),
       st.lists(st.floats(0.0, 1.0), min_size=2, max_size=16))
@settings(max_examples=50, deadline=None)
def test_aggregation_weights_simplex(ent, acc):
    n = min(len(ent), len(acc))
    w = aggregation_weights(ent[:n], acc[:n])
    assert w.shape == (n,)
    assert abs(w.sum() - 1.0) < 1e-9
    assert (w >= 0).all()


@given(st.lists(st.integers(0, 1000), min_size=1, max_size=20))
@settings(max_examples=50, deadline=None)
def test_entropy_bounds(counts):
    h = information_entropy(counts)
    n_nonzero = sum(1 for c in counts if c > 0)
    assert h >= -1e-12
    if n_nonzero:
        assert h <= np.log2(max(n_nonzero, 1)) + 1e-9


@given(st.integers(0, 2**31 - 1), st.lists(floats, min_size=3, max_size=3))
@settings(max_examples=30, deadline=None)
def test_weighted_aggregate_convexity(seed, ws):
    """Aggregate of identical trees is that tree; aggregate stays in hull."""
    rng = np.random.default_rng(seed)
    trees = [{"a": rng.standard_normal(4).astype(np.float32)} for _ in range(3)]
    agg = weighted_aggregate(trees[0], trees, np.asarray(ws))
    lo = np.min([t["a"] for t in trees], axis=0) - 1e-5
    hi = np.max([t["a"] for t in trees], axis=0) + 1e-5
    assert (agg["a"] >= lo).all() and (agg["a"] <= hi).all()
    same = weighted_aggregate(trees[0], [trees[0]] * 3, np.asarray(ws))
    np.testing.assert_allclose(same["a"], trees[0]["a"], atol=1e-6)


@given(st.integers(1, 50), st.integers(1, 50))
@settings(max_examples=30, deadline=None)
def test_latency_monotone_in_intensity(tau1, tau2):
    lm = LatencyModel({"small": 100.0, "large": 400.0}, 50.0, seed=0)
    one = ClientStore(base_speed=[2.0], dataset_size=[100], entropy=[1.0],
                      jitter_sigma=0.0, drift_amp=0.0)
    t1 = lm.local_train_times(one, [0], 0, ["small"], [tau1])[0]
    t2 = lm.local_train_times(one, [0], 0, ["small"], [tau2])[0]
    if tau1 < tau2:
        assert t1 < t2
    lm2 = LatencyModel({"small": 100.0, "large": 400.0}, 50.0, seed=0)
    assert (lm2.local_train_times(one, [0], 0, ["large"], [tau1])[0]
            > lm2.local_train_times(one, [0], 0, ["small"], [tau1])[0])


@given(st.lists(st.floats(-10, 10), min_size=1, max_size=20),
       st.floats(0.0, 0.99))
@settings(max_examples=50, deadline=None)
def test_discounted_returns_bound(rewards, gamma):
    import jax.numpy as jnp
    g = np.asarray(discounted_returns(jnp.asarray(rewards, jnp.float32),
                                      gamma))
    bound = max(abs(r) for r in rewards) / (1 - gamma + 1e-9) + 1e-3
    assert (np.abs(g) <= bound).all()


@given(st.lists(st.integers(1, 64), min_size=1, max_size=4),
       st.sampled_from(["f32", "bf16", "s32", "pred"]))
@settings(max_examples=50, deadline=None)
def test_hlo_shape_bytes(dims, dt):
    bytes_per = {"f32": 4, "bf16": 2, "s32": 4, "pred": 1}[dt]
    s = f"{dt}[{','.join(map(str, dims))}]{{0}}"
    expected = bytes_per * int(np.prod(dims))
    assert shape_bytes(s) == expected


def test_fedavg_weighted_mean_exact():
    t1 = {"a": np.ones(3, np.float32)}
    t2 = {"a": 3 * np.ones(3, np.float32)}
    agg = fedavg_aggregate([t1, t2], sizes=[1, 3])
    np.testing.assert_allclose(agg["a"], 2.5 * np.ones(3), rtol=1e-6)


# --------------------------------------------------------------------- #
# codec round-trip properties
# --------------------------------------------------------------------- #
def _random_tree(rng, scale):
    return {"w": (scale * rng.standard_normal((3, 5))).astype(np.float32),
            "b": (scale * rng.standard_normal(7)).astype(np.float32)}


@given(st.integers(0, 2**31 - 1), st.floats(1e-3, 10.0))
@settings(max_examples=30, deadline=None)
def test_identity_codec_roundtrip_bit_exact(seed, scale):
    rng = np.random.default_rng(seed)
    params = _random_tree(rng, scale)
    ref = _random_tree(rng, scale)
    codec = make_codec("identity")
    enc, state = codec.encode(params, ref, None, seed=0, client=1,
                              round_idx=2, tag="local")
    out = codec.decode(enc, ref)
    assert state is None
    for k in params:
        assert np.asarray(out[k]).tobytes() == params[k].tobytes()
    assert enc.wire_bytes == sum(v.size for v in params.values()) * 4.0


@given(st.integers(0, 2**31 - 1), st.floats(1e-3, 10.0),
       st.integers(0, 1000))
@settings(max_examples=30, deadline=None)
def test_int8_quantize_error_within_one_level(seed, scale, round_idx):
    """Stochastic rounding to 8-bit levels is off by at most one level
    (= qt.scale) elementwise, for any tensor and entropy."""
    rng = np.random.default_rng(seed)
    x = (scale * rng.standard_normal(257)).astype(np.float32)
    qt = quantize(x, 8, 0, seed, round_idx)
    err = np.abs(dequantize(qt).astype(np.float64) - x.astype(np.float64))
    assert err.max() <= qt.scale * (1.0 + 1e-5) + 1e-7
    # constant tensors round-trip exactly (scale falls back to 1, q = 0)
    c = np.full(5, float(x[0]), np.float32)
    np.testing.assert_array_equal(dequantize(quantize(c, 8, seed)), c)


@given(st.integers(0, 2**31 - 1), st.floats(1e-2, 2.0),
       st.integers(2, 8))
@settings(max_examples=20, deadline=None)
def test_int8_ef_residual_bounded(seed, amp, rounds):
    """Error feedback keeps the carried residual within one quantization
    level of the EF-corrected delta each round, and the cumulative decoded
    update deviates from the true cumulative delta by exactly the final
    residual (telescoping)."""
    rng = np.random.default_rng(seed)
    codec = make_codec("int8")
    ref = {"a": np.zeros(64, np.float32)}
    state = None
    true_cum = np.zeros(64, np.float64)
    dec_cum = np.zeros(64, np.float64)
    for t in range(rounds):
        delta = (amp * rng.standard_normal(64)).astype(np.float32)
        prev = state[0] if state is not None else np.zeros(64, np.float32)
        corrected = delta.astype(np.float64) + prev.astype(np.float64)
        params = {"a": ref["a"] + delta}
        enc, state = codec.encode(params, ref, state, seed=0, client=3,
                                  round_idx=t, tag="local")
        dec = codec.decode(enc, ref)
        dec_cum += np.asarray(dec["a"], np.float64)
        true_cum += delta.astype(np.float64)
        level = max(np.ptp(corrected) / 255.0, 0.0)
        assert np.abs(state[0]).max() <= level * (1.0 + 1e-4) + 1e-6
    gap = np.abs(dec_cum - true_cum)
    np.testing.assert_allclose(gap, np.abs(state[0].astype(np.float64)),
                               atol=rounds * 1e-5)
    assert gap.max() <= level * (1.0 + 1e-4) + rounds * 1e-5


@given(st.lists(floats, min_size=2, max_size=12),
       st.lists(st.floats(0.0, 1.0), min_size=2, max_size=12),
       st.integers(0, 20), st.floats(0.1, 1.0))
@settings(max_examples=40, deadline=None)
def test_staleness_weights_normalized_monotone(ent, acc, tau, exponent):
    n = min(len(ent), len(acc))
    ent, acc = ent[:n], acc[:n]
    # staleness=None is exactly Eq. 38 (no discount, no renormalization)
    np.testing.assert_array_equal(staleness_weights(ent, acc, None),
                                  aggregation_weights(ent, acc))
    # any staleness vector still lands on the simplex
    stale = [(tau + i) % 23 for i in range(n)]
    w = staleness_weights(ent, acc, stale, exponent)
    assert abs(w.sum() - 1.0) < 1e-9
    assert (w >= 0).all()
    # equal-quality clients: the staler one never outweighs the fresher
    ent2, acc2 = [ent[0]] * 2, [acc[0]] * 2
    w2 = staleness_weights(ent2, acc2, [tau, tau + 1], exponent)
    assert w2[0] > w2[1]
    w3 = staleness_weights(ent2, acc2, [tau, tau], exponent)
    np.testing.assert_allclose(w3, [0.5, 0.5], atol=1e-12)


# --------------------------------------------------------------------- #
# observability quantiles vs numpy.percentile (DESIGN.md §16)
# --------------------------------------------------------------------- #
@given(st.lists(st.integers(0, 30), min_size=0, max_size=40),
       st.floats(0.01, 0.99))
@settings(max_examples=60, deadline=None)
def test_int_histogram_quantile_is_inverted_cdf(vals, q):
    """IntHistogram.quantile is exactly the smallest observed value whose
    cumulative count reaches q*total — numpy's inverted_cdf method."""
    ih = IntHistogram("ih")
    for v in vals:
        ih.observe(v)
    got = ih.quantile(q)
    if not vals:
        assert got is None
    else:
        assert got == float(np.percentile(vals, 100.0 * q,
                                          method="inverted_cdf"))
        assert got == float(min(vals)) if len(set(vals)) == 1 else True


@given(st.lists(st.floats(0.0, 20.0), min_size=0, max_size=50),
       st.floats(0.01, 0.99))
@settings(max_examples=60, deadline=None)
def test_histogram_quantile_within_bucket_width(vals, q):
    """The interpolated Histogram.quantile shares a bucket with the
    rank-q order statistic (numpy's inverted_cdf), so it lands within
    one bucket width of it — and inside the observed value range thanks
    to the min/max clamping of the open outer buckets."""
    edges = (1.0, 4.0, 10.0)
    h = Histogram("h", edges=edges)
    for v in vals:
        h.observe(v)
    got = h.quantile(q)
    if not vals:
        assert got is None
        return
    lo0, hi_last = min(min(vals), edges[0]), max(max(vals), edges[-1])
    widths = ([edges[0] - min(lo0, edges[0])]
              + [b - a for a, b in zip(edges[:-1], edges[1:])]
              + [max(hi_last, edges[-1]) - edges[-1]])
    want = float(np.percentile(vals, 100.0 * q, method="inverted_cdf"))
    assert abs(got - want) <= max(widths) + 1e-9
    assert min(vals) - 1e-9 <= got <= max(vals) + 1e-9
    if len(vals) == 1:                 # size-1: exactly that value
        assert got == pytest.approx(vals[0])


@given(st.lists(st.floats(1e-6, 10.0), min_size=0, max_size=40))
@settings(max_examples=60, deadline=None)
def test_reservoir_and_latency_stats_match_numpy(seconds):
    """latency_stats (and Reservoir.stats on top of it) reports exactly
    numpy.percentile of the millisecond samples, rounded at 3 dp."""
    res = Reservoir("r")
    for s in seconds:
        res.observe(s)
    stats = res.stats()
    assert stats == latency_stats(seconds)
    if not seconds:
        assert stats is None
        return
    ms = np.asarray(seconds) * 1e3
    assert stats["n"] == len(seconds)
    assert stats["p50_ms"] == round(float(np.percentile(ms, 50)), 3)
    assert stats["p99_ms"] == round(float(np.percentile(ms, 99)), 3)
    assert stats["max_ms"] == round(float(ms.max()), 3)
    if len(seconds) == 1:              # size-1: every stat is the sample
        assert stats["p50_ms"] == stats["p99_ms"] == stats["max_ms"]
