"""The traffic generator against the paper's calibration and against a
plain binary search over the same fixed-point CDF."""
import jax
import numpy as np
import pytest

from bench import generator, work
from repro.core import access_patterns

ROWS, BATCH, POOLING = 500_000, 2048, 150


@pytest.mark.parametrize("hotness", ["med_hot", "random"])
def test_unique_access_matches_the_calibration(hotness):
    cfg = {"rows": ROWS, "num_tables": 2, "pooling": POOLING,
           "dense_features": 13}
    made = generator.make_traffic(cfg, {"hotness": hotness}, seed=2**33 + 5,
                                  batches=1, batch=BATCH)
    got = work.distinct_rows(made.indices, ROWS) * 100.0 / ROWS
    want = access_patterns.expected_unique_pct(
        ROWS, generator.HOTNESS_ALPHA[hotness], BATCH * POOLING)
    # one batch of 307,200 draws: the count of distinct rows is a sum of
    # 500,000 nearly independent indicators, its spread ~0.1 % of rows
    assert np.all(np.abs(got - want) < 0.5), (got, want)


def test_alphas_are_the_calibrated_ones():
    for hotness, target in access_patterns.PAPER_UNIQUE_PCT.items():
        if hotness in generator.HOTNESS_ALPHA and hotness != "random":
            assert generator.HOTNESS_ALPHA[hotness] == pytest.approx(
                access_patterns.calibrate_alpha(target), rel=1e-9)


@pytest.mark.parametrize("hotness", ["high_hot", "med_hot", "random"])
def test_bucket_search_equals_binary_search(hotness):
    rows = 50_000
    cdf, bucket, shift, span = generator.zipf_tables(
        generator.HOTNESS_ALPHA[hotness], rows)
    perms = np.arange(rows, dtype=np.int32)[None]
    key = jax.random.key(3)
    ids, _ = generator._draw_batch(key, cdf, bucket, perms, batch=64,
                                   pooling=50, dense_features=1,
                                   shift=shift, span=span)
    k_rows, _ = jax.random.split(key)
    u = np.asarray(jax.random.bits(k_rows, (64, 1, 50), np.uint32))
    want = np.searchsorted(cdf, u.ravel(), side="left").reshape(u.shape)
    np.testing.assert_array_equal(np.asarray(ids), want)


def test_same_seed_same_traffic_and_large_seeds_differ():
    cfg = {"rows": 1000, "num_tables": 2, "pooling": 4, "dense_features": 3}
    a = generator.make_traffic(cfg, {"hotness": "med_hot"}, 2**40 + 1, 2, 8)
    b = generator.make_traffic(cfg, {"hotness": "med_hot"}, 2**40 + 1, 3, 8)
    c = generator.make_traffic(cfg, {"hotness": "med_hot"}, 1, 2, 8)
    np.testing.assert_array_equal(a.indices, b.indices[:16])
    np.testing.assert_array_equal(a.dense, b.dense[:16])
    assert not np.array_equal(a.indices, c.indices)


def test_open_loop_arrivals_have_a_fixed_count():
    t1 = generator.open_loop_due({"rate_qps": 100.0}, 3.0, 1)
    t2 = generator.open_loop_due({"rate_qps": 100.0}, 3.0, 2**35)
    assert len(t1) == len(t2) == 300
    assert np.all(np.diff(t1) >= 0) and 0 < t1[0] and t1[-1] < 3.0
    assert not np.array_equal(t1, t2)
