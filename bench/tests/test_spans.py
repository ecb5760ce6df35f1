"""The readers of the program's `serve.*` spans (bench/spans.py): their
arithmetic over hand-built spans, their silence where the program opens
no such span, and the statistics read back from a trace recorded on the
CPU around the harness's own serving loop."""
import types

import jax
import numpy as np
import pytest

from bench import loops, run, spans, trace_reduce
from bench.references import dlrm
from bench.spans import Span
from bench.tests.test_harness import TRAFFIC, tiny_config

READERS = ["batch_wait_ms", "serve_host_ms", "assemble_ms", "h2d_ms"]


def batch(t0, n, wait_s_sum, put=True):
    """One served batch of 100 ns: assemble 10, stage 5, forward 60 (a
    put of 20 inside), record 15, and its window span around it."""
    out = [Span("bench.poll", t0 - 5, t0 + 105),
           Span("serve.batch", t0, t0 + 100,
                {"queries": n, "padded": 8, "wait_s_sum": wait_s_sum,
                 "wait_s_max": wait_s_sum}),
           Span("serve.assemble", t0, t0 + 10),
           Span("serve.stage", t0 + 10, t0 + 15),
           Span("serve.forward", t0 + 20, t0 + 80),
           Span("serve.record", t0 + 85, t0 + 100)]
    if put:
        out.append(Span("serve.put", t0 + 20, t0 + 40,
                        {"bytes": 1024}))
    return out


def two_batches(put=True):
    return batch(0, 8, 0.8, put) + batch(200, 2, 0.5, put)


def value(name, found):
    return run.load_reader(name).value(found)


def test_readers_over_hand_built_spans():
    found = two_batches()
    # 1.3 s of waits over 10 queries
    assert value("batch_wait_ms", found) == pytest.approx(130.0)
    # each batch: 100 ns less a 60 ns forward
    assert value("serve_host_ms", found) == pytest.approx(40e-6)
    assert value("assemble_ms", found) == pytest.approx(10e-6)
    assert value("h2d_ms", found) == pytest.approx(20e-6)


def test_the_split_engine_reports_no_put():
    assert value("h2d_ms", two_batches(put=False)) is None
    assert value("assemble_ms", two_batches(put=False)) is not None


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_spans_reports_nothing(name):
    """The parent of these spans opens only the window spans."""
    bare = [s for s in two_batches() if not s.name.startswith("serve.")]
    assert value(name, bare) is None
    assert value(name, []) is None


@pytest.mark.parametrize("name", READERS)
def test_an_untraced_run_reports_nothing(name):
    assert run.load_reader(name).read(types.SimpleNamespace(
        summary=None)) is None


def test_clip_keeps_the_statistics():
    s = [Span("serve.batch", -10, 30, {"queries": 3}),
         Span("serve.put", 40, 60), Span("bench.submit", 90, 120)]
    assert spans.clip(s, 0, 100) == [
        Span("serve.batch", 0, 30, {"queries": 3}), Span("serve.put", 40, 60),
        Span("bench.submit", 90, 100)]


def test_spans_of_a_recorded_cpu_trace(tmp_path):
    """The harness's closed loop over a tiny `device` session, traced on
    the CPU: every window batch is one `serve.batch` span with its
    statistics, and the readers agree with the loop's own records."""
    from bench.deploy import Deployment
    cfg = tiny_config()
    seed = 2**31 + 5
    made = run.generator.make_traffic(cfg, TRAFFIC, seed, 4, cfg["batch"])
    params = dlrm.init_params(seed, cfg)
    dep = Deployment(cfg, params, trace=None)
    server = loops.Server(dep.session)
    queries = run.make_queries(made)
    with jax.profiler.trace(str(tmp_path)):
        window = loops.closed_loop(server, queries, 0.3,
                                   TRAFFIC["queued_batches"] * cfg["batch"])
    dep.close()
    found = spans.load(trace_reduce.find_xplane(str(tmp_path)))

    batches = spans.named(found, spans.BATCH)
    assert len(batches) == len(window.batches) >= 2
    for s, b in zip(sorted(batches, key=lambda s: s.start_ns),
                    window.batches):
        assert s.stats["queries"] == len(b.qids) == cfg["batch"]
        assert s.stats["padded"] == cfg["batch"]
        # popped inside the poll: between its start and its end
        waits = s.stats["wait_s_sum"] / len(b.qids)
        assert np.mean(b.start - b.due) <= waits <= np.mean(b.end - b.due)
    puts = spans.named(found, "serve.put")
    per_batch = cfg["batch"] * (cfg["dense_features"]
                                + cfg["num_tables"] * cfg["pooling"]) * 4
    assert [p.stats["bytes"] for p in puts] == [per_batch] * len(batches)
    for name in READERS:
        assert value(name, found) > 0
    host = 1e3 * np.mean([b.end - b.start - b.service_s
                          for b in window.batches])
    assert value("serve_host_ms", found) <= host
