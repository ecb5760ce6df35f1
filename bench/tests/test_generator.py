"""The traffic generator against the paper's calibration and against a
plain binary search over the same fixed-point CDF, the cells' traffic
against digests taken before per-table geometry, and tables that each
have their own rows, bag size and hotness."""
import hashlib
import json
import os

import jax
import numpy as np
import pytest

from bench import generator, work
from repro.core import access_patterns

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
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


def load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


# SHA-256 of the first two batches' `indices` then `dense` bytes, drawn
# for dlrm-prod-device32 by the generator of the parent commit of the
# per-table geometry (792830a), before that change: the cells' traffic
# may not move.
PINNED = [
    ("medhot-sat", 1,
     "52e37536f867eaf3bcb763422aa93d749cb6f3b48b744edb16c064e492086a74"),
    ("medhot-sat", 2**33 + 5,
     "e69df31a9264c2ac48f0ded54b76d10f5f3facbf37358e93b74b602671dba661"),
    ("random-sat", 2**31 + 77,
     "61705369ac66524f602ef7cb567743e3f47d7268e7eeb24c132892efa3c44652"),
]


@pytest.mark.parametrize("traffic,seed,digest", PINNED)
def test_the_cells_traffic_is_unchanged(traffic, seed, digest):
    cfg = load("bench/configs/dlrm-prod-device32.json")
    made = generator.make_traffic(cfg, load(f"bench/traffic/{traffic}.json"),
                                  seed, 2, cfg["batch"])
    assert made.indices.shape == (2 * 2048, 32, 150)
    assert made.indices.dtype == np.int32
    h = hashlib.sha256(made.indices.tobytes())
    h.update(made.dense.tobytes())
    assert h.hexdigest() == digest


RAGGED = {"num_tables": 3, "rows": [10, 1000, 3], "pooling": [1, 5, 2],
          "dense_features": 3}
RAGGED_TRAFFIC = {"hotness": ["random", "high_hot", "med_hot"]}


def test_ragged_tables_lie_in_their_columns_and_rows():
    made = generator.make_traffic(RAGGED, RAGGED_TRAFFIC, 2**35 + 3, 3, 64)
    assert made.indices.shape == (192, 8) and made.indices.dtype == np.int32
    assert made.dense.shape == (192, 3)
    for ids, rows in zip(work.bags(made.indices, RAGGED["pooling"]),
                         RAGGED["rows"]):
        assert ids.min() >= 0 and ids.max() < rows
    # every row of the tiny tables is drawn
    assert work.distinct_rows(made.indices, RAGGED["rows"],
                              RAGGED["pooling"])[[0, 2]].tolist() == [10, 3]


def test_each_table_has_its_own_hotness():
    """A hotness list over one stack (Table VII's mixes): [N, T, L]."""
    cfg = dict(RAGGED, rows=1000, pooling=5)
    made = generator.make_traffic(
        cfg, {"hotness": ["random", "high_hot", "random"]}, 9, 1, 64)
    assert made.indices.shape == (64, 3, 5)
    d = work.distinct_rows(made.indices, 1000)
    # 320 draws from 1,000 rows: ~274 distinct uniform, a few dozen hot
    assert d[1] < d[0] / 2 and d[1] < d[2] / 2, d


def test_ragged_traffic_is_the_same_for_a_seed():
    a = generator.make_traffic(RAGGED, RAGGED_TRAFFIC, 2**40 + 1, 2, 16)
    b = generator.make_traffic(RAGGED, RAGGED_TRAFFIC, 2**40 + 1, 3, 16)
    c = generator.make_traffic(RAGGED, RAGGED_TRAFFIC, 2, 2, 16)
    np.testing.assert_array_equal(a.indices, b.indices[:32])
    np.testing.assert_array_equal(a.dense, b.dense[:32])
    assert not np.array_equal(a.indices, c.indices)


def test_equal_lists_draw_what_one_value_draws():
    """Per-table lists that agree are the stacked draw, laid out flat."""
    cfg = {"num_tables": 2, "rows": 1000, "pooling": 4, "dense_features": 3}
    one = generator.make_traffic(cfg, {"hotness": "med_hot"}, 7, 2, 8)
    lists = generator.make_traffic(
        dict(cfg, rows=[1000, 1000], pooling=[4, 4]),
        {"hotness": ["med_hot", "med_hot"]}, 7, 2, 8)
    assert one.indices.shape == (16, 2, 4) and lists.indices.shape == (16, 8)
    np.testing.assert_array_equal(one.indices.reshape(16, 8), lists.indices)
    np.testing.assert_array_equal(one.dense, lists.dense)


@pytest.mark.parametrize("cfg,traffic,key", [
    (dict(RAGGED, rows=[10, 1000]), RAGGED_TRAFFIC, "'rows'"),
    (dict(RAGGED, pooling=[1, 5, 2, 2]), RAGGED_TRAFFIC, "'pooling'"),
    (RAGGED, {"hotness": ["random", "med_hot"]}, "'hotness'"),
])
def test_a_list_of_the_wrong_length_is_refused(cfg, traffic, key):
    with pytest.raises(ValueError, match=key):
        generator.make_traffic(cfg, traffic, 1, 1, 8)
