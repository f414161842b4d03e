"""Traffic is a function of the seed; percentiles cover every request."""

import math

import numpy as np
import pytest

from bench import loadgen
from bench.tests.tiny import tiny_spec


@pytest.mark.parametrize("pattern", ["poisson", "bursty", "uniform"])
def test_open_loop_schedule_is_a_function_of_the_mix(pattern):
    mix = {"rate_qps": 80.0, "arrivals": pattern, "arrival_seed": 11}
    a = loadgen.open_loop_schedule(mix, 5.0)
    b = loadgen.open_loop_schedule(dict(mix), 5.0)
    np.testing.assert_array_equal(a, b)
    assert a.size and a.max() < 5.0 and np.all(np.diff(a) >= 0)
    if pattern != "uniform":
        c = loadgen.open_loop_schedule(dict(mix, arrival_seed=12), 5.0)
        assert a.shape != c.shape or not np.array_equal(a, c)


def test_poisson_rate_is_the_mix_rate():
    n = np.mean([loadgen.open_loop_schedule(
        {"rate_qps": 60.0, "arrivals": "poisson", "arrival_seed": s},
        30.0).size for s in range(20)])
    assert abs(n - 1800) < 60


def test_queries_and_table_are_a_function_of_the_seed():
    spec = tiny_spec()
    cfg, mix = spec["config"], spec["mix"]
    t1 = loadgen.make_table(cfg, 12345)
    t2 = loadgen.make_table(cfg, 12345)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    q1, g1 = loadgen.make_queries(t1, cfg, mix, 12345, 32)
    q2, g2 = loadgen.make_queries(t2, cfg, mix, 12345, 32)
    np.testing.assert_array_equal(q1, q2)
    np.testing.assert_array_equal(g1, g2)
    q3, _ = loadgen.make_queries(t1, cfg, mix, 12346, 32)
    assert not np.array_equal(q1, q3)


@pytest.mark.parametrize("table_dtype", [None, "bfloat16"])
def test_table_and_queries_obey_the_stated_value_range(table_dtype):
    spec = tiny_spec(table_dtype=table_dtype)
    cfg, mix = spec["config"], spec["mix"]
    V = np.asarray(loadgen.make_table(cfg, 3), np.float64)
    Q, _ = loadgen.make_queries(V, cfg, mix, 3, 64)
    assert np.abs(Q).max() <= cfg["qmax_hint"]
    bound = loadgen.value_range(cfg) / 2
    assert np.abs(V).max() * np.abs(Q).max() <= bound
    assert np.max(np.abs(Q @ V.T)) / cfg["d"] <= bound


def _draw(cfg, seed):
    """The fp32 draw as the formula states it: normal, clipped, times std."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(loadgen.seed_words(seed, loadgen.TABLE))
    z = jax.random.normal(key, (cfg["rows"], cfg["d"]), jnp.float32)
    lim = float(cfg["trunc_sigmas"])
    return np.asarray(jnp.clip(z, -lim, lim) * jnp.float32(cfg["init_std"]))


def test_an_fp32_table_is_the_draw_bit_for_bit():
    cfg = tiny_spec()["config"]
    assert "table_dtype" not in cfg
    V = loadgen.make_table(cfg, 2 ** 33 + 5)
    assert V.dtype == np.float32
    np.testing.assert_array_equal(
        np.asarray(V).view(np.uint32), _draw(cfg, 2 ** 33 + 5).view(np.uint32))


def test_a_bf16_table_is_the_draw_rounded_to_nearest_even():
    import ml_dtypes

    cfg = tiny_spec(table_dtype="bfloat16")["config"]
    V = loadgen.make_table(cfg, 2 ** 33 + 5)
    assert V.dtype == ml_dtypes.bfloat16
    cap = loadgen.bf16_cap(cfg["trunc_sigmas"] * cfg["init_std"])
    up = np.asarray(cap, ml_dtypes.bfloat16)
    up = float(np.nextafter(up, np.ones_like(up)))   # the next bf16 above
    assert cap <= cfg["trunc_sigmas"] * cfg["init_std"] < up
    rounded = _draw(cfg, 2 ** 33 + 5).astype(ml_dtypes.bfloat16)  # RNE
    want = np.clip(rounded.astype(np.float32), -cap, cap)
    np.testing.assert_array_equal(np.asarray(V, np.float32), want)
    # the clip moves only the few draws that rounded past the cap
    assert np.mean(np.asarray(V, np.float32) != rounded.astype(np.float32)) \
        < 1e-4


@pytest.mark.parametrize("name", ["float16", "fp32", "int8"])
def test_an_unknown_table_dtype_is_refused(name):
    cfg = tiny_spec(table_dtype=name)["config"]
    with pytest.raises(ValueError, match="table_dtype"):
        loadgen.make_table(cfg, 1)


def test_targets_stand_out_as_the_mix_says():
    spec = tiny_spec()
    cfg, mix = spec["config"], spec["mix"]
    V = np.asarray(loadgen.make_table(cfg, 5), np.float64)
    Q, tgt = loadgen.make_queries(V, cfg, mix, 5, 64)
    s = Q.astype(np.float64) @ V[:cfg["n_valid"]].T
    z = (s[np.arange(64), tgt] - s.mean(1)) / s.std(1)
    lo, hi = mix["target_z"]
    assert np.median(z) > lo - 1.5 and np.median(z) < hi + 1.5
    assert np.all(np.argmax(s, axis=1) == tgt)


def test_percentiles_count_missing_requests_as_slowest():
    lat = [i * 1e-3 for i in range(1, 20)]          # 1..19 ms
    p50, p95 = loadgen.percentiles(lat, missing=0)
    assert p50 == pytest.approx(10.0) and p95 == pytest.approx(19.0)
    # one refused request of 20: p95 is the 19th of 20, still answered
    p50, p95 = loadgen.percentiles(lat, missing=1)
    assert p50 == pytest.approx(10.0) and p95 == pytest.approx(19.0)
    # two refused of 21: the 95th percentile lands on a missing one
    p50, p95 = loadgen.percentiles(lat, missing=2)
    assert p50 == pytest.approx(11.0) and math.isinf(p95)
    assert loadgen.percentiles([], missing=3) == [math.inf, math.inf]
