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
