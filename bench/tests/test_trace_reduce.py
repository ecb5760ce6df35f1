"""The reduction from trace events to busy time, per-operation time and
labelled idle gaps."""
import os

import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Event


def small_trace():
    # host thread: two window spans; inside the first, a nested "assemble"
    host = [Event("bench.poll", 0, 100), Event("assemble", 5, 30),
            Event("bench.submit", 100, 110), Event("bench.poll", 110, 200)]
    # device: overlapping ops 10-40 and 35-50, a kernel 60-90, a dense op
    # 120-130, an op partly before the window
    dev = [Event("fusion.1", -20, 2), Event("kernel_wrapper", 10, 40),
           Event("fusion.2", 35, 50), Event("kernel_wrapper", 60, 90),
           Event("fusion.3", 120, 130)]
    return tr.Trace([dev], host)


def test_window_union_and_gaps():
    t = small_trace()
    lo, hi = tr.window(t.host_events)
    assert (lo, hi) == (0, 200)
    inside = tr.clip(t.device_ops[0], lo, hi)
    assert tr.union(inside) == [(0, 2), (10, 50), (60, 90), (120, 130)]
    assert tr.gaps(tr.union(inside), lo, hi) == [
        (2, 10), (50, 60), (90, 120), (130, 200)]


def test_summary_busy_ops_and_labels():
    s = tr.summarize(small_trace())
    assert s.window_s == pytest.approx(200e-9)
    assert s.busy_s == pytest.approx((2 + 40 + 30 + 10) * 1e-9)
    assert s.seconds_matching(lambda n: "kernel" in n) == pytest.approx(
        60e-9)
    assert s.ops["fusion.1"] == pytest.approx(2e-9)     # clipped at 0
    # gap 2-10 lies in "assemble"; 50-60 and 90-120 straddle the first
    # poll's end: 55 is in it, 105 in "bench.submit"; 130-200 in the
    # second poll
    assert s.gap_labels == pytest.approx({"assemble": 8e-9,
                                          "bench.poll": 80e-9,
                                          "bench.submit": 30e-9})
    assert s.top_ops(2)[0] == ["kernel_wrapper", pytest.approx(60e-9)]


def test_a_trace_without_window_spans_is_refused():
    t = small_trace()
    t.host_events = [e for e in t.host_events if e.name != "bench.poll"]
    with pytest.raises(ValueError):
        tr.summarize(t)


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "small_tpu.xplane.pb")


def test_a_recorded_tpu_trace():
    """A trace recorded on one TPU v5e: two `bench.poll` spans around a
    stacked bag kernel (the repo's `embedding_bag_stacked`) and a small
    jitted matmul."""
    t = tr.load(RECORDED)
    assert len(t.device_ops) == 1 and len(t.device_ops[0]) == 12
    s = tr.summarize(t)
    lo, hi = tr.window(t.host_events)
    inside = tr.clip(t.device_ops[0], lo, hi)
    busy = tr.union(inside)
    assert s.busy_s == pytest.approx(sum(b - a for a, b in busy) / 1e9)
    assert s.busy_s <= sum(e.dur_ns for e in inside) / 1e9 + 1e-15
    assert s.busy_s + sum(s.gap_labels.values()) == pytest.approx(s.window_s)
    # the device events of this trace sit ~0.9 ms early against the host
    # spans, so the first kernel launch falls before the window: the
    # reduction counts only what lies inside it
    kernel = [e for e in inside if "tpu_custom_call" in e.name]
    assert len(kernel) == 1
    assert kernel[0].dur_ns == 124675
    assert s.seconds_matching(lambda n: "tpu_custom_call" in n) == \
        pytest.approx(sum(e.dur_ns for e in kernel) / 1e9)
    assert s.top_ops(1)[0][0] == "embedding_bag_stacked.1"
