"""The serving path's profiler spans (docs/serving.md, "Tracing a served
batch"): a small `device` session is served under `jax.profiler.trace`
and the trace is read back. Each served batch opens `serve.batch` with
`serve.assemble`, `serve.stage`, `serve.forward` (holding `serve.put`)
and `serve.record` inside it, in that order; the statistics are the
batch's sums; a poll that serves nothing opens no span; and the answers
are the same bits with the profiler on as off. These run on a virtual
clock, so every poll is serial. Under dispatch-ahead, each batch is
assembled and put once, by the poll that dispatches it, and `ahead`
marks the polls that did."""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import EmbeddingStageConfig
from repro.models.dlrm import DLRM, DLRMConfig
from repro.ps import PSConfig
from repro.serving import BatcherConfig, Query, ServingSession
from repro.serving import server as server_mod
from repro.traffic import VirtualClock

ROWS, TABLES, DIM, POOL, F, B = 200, 3, 16, 4, 13, 32
CHILDREN = ["serve.assemble", "serve.stage", "serve.forward",
            "serve.record"]


def _queries(seed: int, n: int) -> list:
    rng = np.random.default_rng(seed)
    dense = rng.normal(size=(n, F)).astype(np.float32)
    idx = rng.integers(0, ROWS, size=(n, TABLES, POOL)).astype(np.int32)
    return [Query(qid=i, dense=dense[i], indices=idx[i]) for i in range(n)]


def _model(storage: str):
    emb = EmbeddingStageConfig(num_tables=TABLES, rows=ROWS, dim=DIM,
                               pooling=POOL, storage=storage)
    model = DLRM(DLRMConfig(dense_features=F, embedding=emb,
                            bottom_mlp=(32, DIM), top_mlp=(16, 1)))
    return model, model.init(jax.random.PRNGKey(0))


def _session(clock):
    model, params = _model("device")
    return ServingSession(model, params,
                          # a window no test outwaits: the partial batch
                          # is served only when forced
                          batcher=BatcherConfig(max_batch=B,
                                                max_wait_s=100.0),
                          sla_ms=1e6, clock=clock)


def _serve(queries: list, arrivals: np.ndarray) -> tuple:
    """Serve the queries as two full batches and a partial one, with two
    polls that serve nothing; returns the pop time of each served batch,
    the batch service times and the answers by query id."""
    clock = VirtualClock(10.0)
    scores = {}
    pops = []
    with _session(clock) as sess:
        sess.server.on_batch = lambda batch, s: scores.update(
            zip((q.qid for q in batch), np.asarray(s)))
        assert sess.poll() == 0                 # nothing queued
        for q, t in zip(queries, arrivals):
            q.arrival_s = float(t)
            sess.submit(q)
        for force in (False, False, False, True):
            pops.append(clock())
            served = sess.poll(force=force)
            if not served:
                # the partial batch waits out its window: nothing served
                assert not force
                pops.pop()
        latencies = list(sess.stats.batch_latencies_s)
    return pops, latencies, scores


def _host_spans(log_dir: str) -> list:
    """(name, start_ns, end_ns, stats) of the thread holding the spans."""
    path = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                         "*", "*.xplane.pb")))[-1]
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events]
            if any(e[0] == "serve.batch" for e in evs):
                return evs
    raise AssertionError("no serve.batch span in the trace")


def _inside(outer, events, name):
    return [e for e in events if e[0] == name
            and outer[1] <= e[1] and e[2] <= outer[2]]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    queries = _queries(0, 2 * B + 10)
    arrivals = 10.0 - 0.5 + 0.004 * np.arange(len(queries))
    plain = _serve(_queries(0, 2 * B + 10), arrivals)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(log_dir):
        traced = _serve(queries, arrivals)
    return arrivals, plain, traced, _host_spans(log_dir)


def test_each_served_batch_opens_the_spans_nested_in_order(served):
    _, _, (pops, _, _), events = served
    batches = sorted((e for e in events if e[0] == "serve.batch"),
                     key=lambda e: e[1])
    # three batches served; the empty poll and the poll inside the
    # partial batch's window opened none
    assert len(pops) == 3 and len(batches) == 3
    for batch in batches:
        kids = [_inside(batch, events, name) for name in CHILDREN]
        assert [len(k) for k in kids] == [1, 1, 1, 1]
        starts = [k[0][1] for k in kids]
        assert starts == sorted(starts)
        for a, b in zip(kids, kids[1:]):
            assert a[0][2] <= b[0][1]           # one after another
        forward = kids[2][0]
        assert len(_inside(forward, events, "serve.put")) == 1
    # one more put: the session's warm-up forward, outside any poll
    assert len([e for e in events if e[0] == "serve.put"]) == 4


def test_batch_statistics_are_the_batch_sums(served):
    arrivals, _, (pops, _, _), events = served
    batches = sorted((e for e in events if e[0] == "serve.batch"),
                     key=lambda e: e[1])
    bounds = [(0, B), (B, 2 * B), (2 * B, 2 * B + 10)]
    for (lo, hi), pop, batch in zip(bounds, pops, batches):
        stats = batch[3]
        n = hi - lo
        assert stats["queries"] == n
        assert stats["padded"] == B
        waits = pop - arrivals[lo:hi]
        assert stats["wait_s_sum"] == pytest.approx(waits.sum(), abs=1e-9)
        assert stats["wait_s_max"] == pytest.approx(waits.max(), abs=1e-12)
        put = _inside(batch, events, "serve.put")[0]
        assert put[3]["bytes"] == B * F * 4 + B * TABLES * POOL * 4


def test_forward_span_is_the_batch_service_time(served):
    _, _, (_, latencies, _), events = served
    forwards = sorted((e for e in events if e[0] == "serve.forward"),
                      key=lambda e: e[1])
    # the session's warm-up forward runs outside any poll: no span
    assert len(forwards) == len(latencies) == 3
    for f, service in zip(forwards, latencies):
        assert abs((f[2] - f[1]) / 1e9 - service) < 0.5e-3


def test_answers_are_the_same_bits_with_the_profiler_on(served):
    _, (_, _, plain), (_, _, traced), _ = served
    assert sorted(plain) == sorted(traced) == list(range(2 * B + 10))
    got = np.array([traced[k] for k in sorted(traced)])
    want = np.array([plain[k] for k in sorted(plain)])
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


def test_the_host_backed_engine_opens_a_lookup_and_no_put(tmp_path):
    """A `tiered` session: the host lookup runs under `serve.lookup`
    inside `serve.forward`, and nothing is put under `serve.put`."""
    model, params = _model("tiered")
    model.ebc.storage.build(params, PSConfig(hot_rows=16, warm_slots=16))
    with jax.profiler.trace(str(tmp_path)):
        with ServingSession(model, params, sla_ms=1e6,
                            batcher=BatcherConfig(max_batch=B)) as sess:
            for q in _queries(1, B):
                sess.submit(q)
            assert sess.poll() == B
    events = _host_spans(str(tmp_path))
    forward, = [e for e in events if e[0] == "serve.forward"]
    assert len(_inside(forward, events, "serve.lookup")) == 1
    assert not [e for e in events if e[0] == "serve.put"]


def test_a_pipelined_poll_assembles_and_puts_each_batch_once(tmp_path,
                                                            monkeypatch):
    """Dispatch-ahead, on the real clock with the server's accelerator
    check steered: three full batches queued at once take three polls.
    The first dispatches two batches, the second one, the third none;
    each dispatch is one `serve.assemble` and one `serve.put`, the put
    inside the `serve.forward` of the poll that dispatched it, and the
    `ahead` statistic marks the polls that dispatched ahead."""
    monkeypatch.setattr(server_mod, "_device_beside_host", lambda: True)
    with _session(None) as sess:
        for q in _queries(2, 3 * B):
            sess.submit(q)
        with jax.profiler.trace(str(tmp_path)):
            served = [sess.poll() for _ in range(4)]
    assert served == [B, B, B, 0]
    events = _host_spans(str(tmp_path))
    batches = sorted((e for e in events if e[0] == "serve.batch"),
                     key=lambda e: e[1])
    assert [b[3]["ahead"] for b in batches] == [1, 1, 0]
    assert [b[3]["queries"] for b in batches] == [B, B, B]
    dispatched = [2, 1, 0]
    for batch, n in zip(batches, dispatched):
        assert len(_inside(batch, events, "serve.assemble")) == n
        assert len(_inside(batch, events, "serve.put")) == n
        forward, = _inside(batch, events, "serve.forward")
        assert len(_inside(forward, events, "serve.put")) == n
        assert len(_inside(batch, events, "serve.record")) == 1
    puts = [e for e in events if e[0] == "serve.put"]
    assert len(puts) == sum(dispatched) == len(batches)
    assert {p[3]["bytes"] for p in puts} == {B * F * 4 + B * TABLES * POOL * 4}
