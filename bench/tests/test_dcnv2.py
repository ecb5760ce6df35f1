"""The DLRM-DCNv2 configuration's counts by hand, and the two readers this
configuration brings: the dense stages' roofline share and the bag
kernel's nanoseconds a lookup, over hand-built spans and summaries,
silent where the program opens no `lookups` statistic."""
import json
import os
import types

import numpy as np
import pytest

from bench import run, work
from bench.references import dlrm_dcnv2
from bench.spans import Span

ROOT = run.ROOT


def config():
    with open(os.path.join(ROOT, "bench", "configs",
                           "dlrm-dcnv2-share16.json")) as f:
        return json.load(f)


def test_the_cut_of_the_published_tables():
    cfg = config()
    published = cfg["published"]["rows"]
    assert sum(published) == 204_184_588
    # the five 40M-row tables split over 16 chips, the rest whole
    assert cfg["rows"] == [r // 16 if r == 40_000_000 else r
                           for r in published]
    assert sum(work.table_rows(cfg)) == 16_684_588
    assert sum(work.table_pooling(cfg)) == 214
    assert sum(cfg["rows"]) * cfg["dim"] * 4 == 8_542_509_056


def test_counts_by_hand():
    cfg = config()
    b, f, rank = 2048, 27 * 128, 512
    mlp = [(13, 512), (512, 256), (256, 128),
           (f, 1024), (1024, 1024), (1024, 512), (512, 256), (256, 1)]
    cross = 3 * (2 * f * rank + f)
    assert dlrm_dcnv2.weight_count(cfg) == (
        sum(i * o + o for i, o in mlp) + cross) == 16_044_545
    # bags b x 214 x 128; towers; per cross layer two low-rank products
    # and three elementwise operations a feature
    flops = (b * 214 * 128 + sum(2 * b * i * o for i, o in mlp)
             + 3 * (2 * 2 * b * f * rank + 3 * b * f))
    assert work.step_flops(cfg, b) == flops == 65_780_580_352
    # 1000 distinct rows a table, or all of a smaller table's
    distinct = np.minimum(np.array(cfg["rows"]), 1000)
    assert distinct.sum() == 18_369
    assert work.bag_bytes(cfg, distinct, b) == (
        18_369 * 512 + b * 214 * 4 + 26 * b * 512) == 38_420_992
    assert work.step_bytes(cfg, distinct, b) == 38_420_992 + 4 * (
        16_044_545 + b * 13 + b)


def puts(*lookups):
    out = [Span("bench.poll", 0, 1000)]
    for k, n in enumerate(lookups):
        stats = {"bytes": 1024} if n is None else {"bytes": 1024,
                                                   "lookups": n}
        out.append(Span("serve.put", 100 * k, 100 * k + 50, stats))
    return out


def test_bag_ns_per_lookup_over_hand_built_spans():
    reader = run.load_reader("bag_ns_per_lookup")
    # 10 ms of kernel over two full batches of 2048 x 214 ids
    assert reader.value(0.010, puts(438_272, 438_272)) == pytest.approx(
        1e7 / 876_544)


@pytest.mark.parametrize("found", [puts(None, None), puts(438_272, None),
                                   [Span("bench.poll", 0, 10)], []],
                         ids=["no_lookups", "one_without", "no_put", "none"])
def test_bag_ns_per_lookup_is_silent_without_the_statistic(found):
    """The parent program's `serve.put` holds only `bytes`."""
    assert run.load_reader("bag_ns_per_lookup").value(0.010, found) is None
    assert run.load_reader("bag_ns_per_lookup").value(0.0, puts(5)) is None


def fake_run(busy_s, kernel_s, batches, traced=True):
    summary = types.SimpleNamespace(
        busy_s=busy_s,
        seconds_matching=lambda match: kernel_s if match(
            "%embedding_bag.1 = f32[] custom-call(), "
            "custom_call_target=\"tpu_custom_call\"") else 0.0)
    return types.SimpleNamespace(
        cfg=config(), batch=2048,
        summary=summary if traced else None,
        window=types.SimpleNamespace(batches=[object()] * batches),
        peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def test_dense_roofline_by_hand():
    reader = run.load_reader("dense_roofline")
    # ten batches, 30 ms of dense work each
    r = fake_run(busy_s=0.4, kernel_s=0.1, batches=10)
    flops = 65_780_580_352 - 2048 * 214 * 128
    # float32 at `highest`: six bfloat16 passes a product, 2 ms a batch
    least = flops / (197e12 / 6)
    assert least > 16_044_545 * 4 / 819e9
    assert reader.read(r) == pytest.approx(100 * least * 10 / 0.3)
    assert reader.read(fake_run(0.4, 0.1, 10, traced=False)) is None
    assert reader.read(fake_run(0.1, 0.1, 10)) is None


@pytest.mark.parametrize("dtype,precision,passes", [
    ("float32", "highest", 6), ("float32", "high", 3),
    ("float32", "default", 1), ("bfloat16", "highest", 1)])
def test_dense_roofline_peak_follows_the_stated_precision(dtype, precision,
                                                          passes):
    reader = run.load_reader("dense_roofline")
    cfg = dict(config(), dtype=dtype, matmul_precision=precision)
    peaks = {"flops_per_s": 197e12}
    assert reader.flops_peak(cfg, peaks) == pytest.approx(197e12 / passes)
    with pytest.raises(KeyError, match="matmul_precision"):
        reader.flops_peak(dict(cfg, dtype="float32",
                               matmul_precision="tf32"), peaks)
