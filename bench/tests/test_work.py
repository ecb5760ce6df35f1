"""work.py's counts against hand arithmetic for both configurations."""
import json
import os

import numpy as np
import pytest

from bench import work

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def test_distinct_rows_counts_each_row_once():
    idx = np.array([[[1, 1, 2], [0, 0, 0]],
                    [[2, 3, 3], [4, 0, 5]]], np.int32)        # [B=2, T=2, L=3]
    assert work.distinct_rows(idx, 10).tolist() == [3, 3]


@pytest.mark.parametrize("name,tables", [("dlrm-prod-device32", 32),
                                         ("dlrm-prod-tiered4", 4)])
def test_counts_by_hand(name, tables):
    cfg = config(name)
    b, l, d = 2048, 150, 128
    distinct = np.full(tables, 100_000)
    # rows: 100,000 x 128 x 4 B per table; indices 2048 x 150 x 4 B and
    # pooled output 2048 x 128 x 4 B per table
    by_hand = tables * (100_000 * 512 + b * l * 4 + b * d * 4)
    assert work.bag_bytes(cfg, distinct, b) == by_hand
    f = tables + 1
    top_in = 128 + f * (f - 1) // 2
    mlp = [(13, 1024), (1024, 512), (512, 128), (128, 128),
           (top_in, 128), (128, 64), (64, 1)]
    flops = (tables * b * l * d + sum(2 * b * i * o for i, o in mlp)
             + 2 * b * f * f * d)
    assert work.step_flops(cfg, b) == flops
    weights = sum(i * o + o for i, o in mlp)
    assert work.step_bytes(cfg, distinct, b) == by_hand + 4 * (
        weights + b * 13 + b)
    # 100,000 distinct rows per table take 62.5 us per table at
    # 819 GB/s; the step's few GFLOP take about 22 us at 197 TFLOP/s
    # whatever the table count: the step is byte-bound
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = work.least_seconds(flops, work.step_bytes(cfg, distinct, b),
                                  peaks)
    assert bound == "bytes"
    assert t == pytest.approx(work.step_bytes(cfg, distinct, b) / 819e9)


def test_fused_bytes_by_hand():
    cfg = config("dlrm-prod-tiered4")
    # 8 launches of a 2048 x 150 slot-map and a 2048 x 128 output, plus
    # 50,000 resident rows of 512 B
    assert work.fused_bytes(cfg, 50_000, 8, 2048) == (
        50_000 * 512 + 8 * (2048 * 150 * 4 + 2048 * 128 * 4))


# Counted by work.py at the parent commit of the per-table geometry
# (792830a), before the model's counts moved to bench/references/dlrm.py:
# (sum of distinct rows, bag_bytes, step_flops, step_bytes, fused_bytes).
PINNED = {
    "dlrm-prod-device32": (5095387, 2681714176, 4744544256, 2684683780,
                           43819008),
    "dlrm-prod-tiered4": (149316, 85559296, 2814115840, 88263684, 43819008),
}


@pytest.mark.parametrize("name,tables", [("dlrm-prod-device32", 32),
                                         ("dlrm-prod-tiered4", 4)])
def test_counts_are_pinned(name, tables):
    cfg = config(name)
    b, t, l = np.ogrid[:2048, :tables, :150]
    idx = ((b * 7919 + t * 104729 + l * l * 613)
           % (15_000 * (t + 1))).astype(np.int32)
    d = work.distinct_rows(idx, cfg["rows"])
    assert d[:3].tolist() == [15_000, 30_000, 45_000]
    assert (int(d.sum()), work.bag_bytes(cfg, d, 2048),
            work.step_flops(cfg, 2048), work.step_bytes(cfg, d, 2048),
            work.fused_bytes(cfg, 50_000, 8, 2048)) == PINNED[name]


RAGGED = {"num_tables": 3, "rows": [10, 1000, 3], "pooling": [1, 5, 2],
          "dim": 16, "dtype": "float32", "dense_features": 13,
          "bottom_mlp": [32, 16], "top_mlp": [8, 1], "reference": "dlrm"}


def test_ragged_counts_by_hand():
    # two queries, flat layout: table 0 in column 0, table 1 in 1:6,
    # table 2 in 6:8
    idx = np.array([[9, 0, 5, 5, 999, 0, 2, 2],
                    [9, 7, 7, 7, 7, 7, 0, 1]], np.int32)
    d = work.distinct_rows(idx, RAGGED["rows"], RAGGED["pooling"])
    assert d.tolist() == [1, 4, 3]
    # 8 distinct rows of 16 x 4 B; 2 x 8 ids of 4 B; 2 x 3 pooled rows
    assert work.bag_bytes(RAGGED, d, 2) == 8 * 64 + 2 * 8 * 4 + 2 * 3 * 64
    # bags 2 x 8 x 16; towers 13-32-16 and (16 + 6)-8-1; Gram 2 x 4 x 4 x 16
    mlp = [(13, 32), (32, 16), (22, 8), (8, 1)]
    assert work.step_flops(RAGGED, 2) == (
        2 * 8 * 16 + sum(2 * 2 * i * o for i, o in mlp) + 2 * 2 * 16 * 16)
    assert work.step_bytes(RAGGED, d, 2) == work.bag_bytes(RAGGED, d, 2) + 4 * (
        sum(i * o + o for i, o in mlp) + 2 * 13 + 2)


def test_both_layouts_count_alike():
    idx = np.arange(2 * 3 * 4, dtype=np.int32).reshape(2, 3, 4) % 5
    want = work.distinct_rows(idx, 5).tolist()
    assert work.distinct_rows(idx, np.array([5, 5, 5])).tolist() == want
    assert work.distinct_rows(idx.reshape(2, 12), [5, 5, 5],
                              [4, 4, 4]).tolist() == want
