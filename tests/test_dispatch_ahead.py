"""Dispatch-ahead in `InferenceServer.poll` (docs/serving.md, "When a poll
dispatches ahead"): on a device-resident engine a poll dispatches the
next ready batch before it waits for the current one, and answers one
batch. A small DLRM on the `device` backend, on the CPU; the tests steer
the server's accelerator check, which the CPU backend fails, so that the
poll takes the path it takes on a chip.

Pinned here: the answers are the bits, the qids and the order of a
serial run; a query leaves the queue when it is answered, so draining
the queue answers every query and `force` never leaves a batch in flight;
an open loop, a virtual clock and a host-backed engine never dispatch
ahead; the update barrier serves a batch in flight under the old
version; admission counts the queries in flight."""
import numpy as np
import jax
import pytest

from repro import serving
from repro.core import EmbeddingStageConfig
from repro.models.dlrm import DLRM, DLRMConfig
from repro.ps import PSConfig
from repro.serving import (BatcherConfig, Query, QueryShedError,
                           ServingSession)
from repro.serving import server as server_mod
from repro.traffic import VirtualClock

ROWS, TABLES, DIM, POOL, F, B = 200, 3, 16, 4, 13, 16


@pytest.fixture
def on_chip(monkeypatch):
    """The server sees an accelerator, as it does on the chip."""
    monkeypatch.setattr(server_mod, "_device_beside_host", lambda: True)


def _queries(seed: int, n: int, qid0: int = 0) -> list:
    rng = np.random.default_rng(seed)
    dense = rng.normal(size=(n, F)).astype(np.float32)
    idx = rng.integers(0, ROWS, size=(n, TABLES, POOL)).astype(np.int32)
    return [Query(qid=qid0 + i, dense=dense[i], indices=idx[i])
            for i in range(n)]


def _session(storage="device", clock=None, max_wait_s=100.0, **batcher):
    emb = EmbeddingStageConfig(num_tables=TABLES, rows=ROWS, dim=DIM,
                               pooling=POOL, storage=storage)
    model = DLRM(DLRMConfig(dense_features=F, embedding=emb,
                            bottom_mlp=(32, DIM), top_mlp=(16, 1)))
    params = model.init(jax.random.PRNGKey(0))
    if storage != "device":
        model.ebc.storage.build(params, PSConfig(hot_rows=16,
                                                 warm_slots=16))
    return ServingSession(
        model, params, sla_ms=1e6, clock=clock,
        batcher=BatcherConfig(max_batch=B, max_wait_s=max_wait_s,
                              **batcher))


def _tap(sess) -> list:
    """(qids, scores) of each answered batch, in the order answered."""
    out = []
    sess.server.on_batch = lambda batch, s: out.append(
        ([q.qid for q in batch], np.array(s)))
    return out


def _serve(sess, queries, force: bool) -> tuple:
    """Queue the queries at once, answer them with polls; the answers,
    and the queries in flight after each poll."""
    answers = _tap(sess)
    for q in queries:
        sess.submit(q)
    flying = []
    while sess.server.batcher.queue:
        assert sess.poll(force=force)
        flying.append(sess.server.batcher.in_flight)
    return answers, flying


def test_pipelined_answers_are_the_serial_bits_in_order(on_chip):
    """Five full batches queued at once: the pipelined polls dispatch
    ahead on every poll but the last, and answer the same qids and the
    same logits, bit for bit and batch for batch, as serial polls."""
    with _session() as sess:
        serial, flying = _serve(sess, _queries(0, 5 * B), force=True)
        assert flying == [0] * 5
        piped, flying = _serve(sess, _queries(0, 5 * B), force=False)
        assert flying == [B, B, B, B, 0]
        assert sess.stats.served == 10 * B
    assert [q for q, _ in piped] == [q for q, _ in serial] \
        == [list(range(k * B, (k + 1) * B)) for k in range(5)]
    for (_, got), (_, want) in zip(piped, serial):
        np.testing.assert_array_equal(got.view(np.uint32),
                                      want.view(np.uint32))


@pytest.mark.parametrize("drain", ["forced_polls", "server_drain",
                                   "session_drain"])
def test_draining_after_a_dispatch_ahead_answers_every_query(on_chip,
                                                             drain):
    with _session() as sess:
        answers = _tap(sess)
        for q in _queries(1, 3 * B + 5):
            sess.submit(q)
        assert sess.poll() == B
        # the second batch is in flight and still queued
        batcher = sess.server.batcher
        assert batcher.in_flight == B and len(batcher.queue) == 2 * B + 5
        assert sess.stats.request_queue_len == 2 * B + 5
        if drain == "forced_polls":
            while batcher.queue:
                sess.poll(force=True)
        elif drain == "server_drain":
            sess.server.drain()
        else:
            sess.drain()
        assert not batcher.queue and batcher.in_flight == 0
        assert sess.server._in_flight is None
        assert sess.stats.served == 3 * B + 5
    qids = [q for batch, _ in answers for q in batch]
    assert qids == list(range(3 * B + 5))


def test_an_open_loop_leaves_nothing_in_flight(on_chip):
    """Queries submitted between polls, each poll's batch takes all that
    waits: there is nothing to dispatch ahead, and the poll is serial."""
    with _session(max_wait_s=0.0) as sess:
        answers = _tap(sess)
        sizes = [5, B, 3, 11, 1]
        qid = 0
        for n in sizes:
            for q in _queries(qid, n, qid0=qid):
                sess.submit(q)
            qid += n
            assert sess.poll() == n
            assert sess.server.batcher.in_flight == 0
            assert sess.server._in_flight is None
        assert sess.poll() == 0
    assert [len(batch) for batch, _ in answers] == sizes


@pytest.mark.parametrize("setting", ["virtual_clock", "host_backed",
                                     "cpu_backend"])
def test_no_dispatch_ahead_where_it_is_not_sound(monkeypatch, setting):
    """A virtual clock advances by serial service time, a host-backed
    engine looks up synchronously, and the CPU backend has no device
    beside the host: each poll answers the batch it dispatched."""
    if setting != "cpu_backend":
        monkeypatch.setattr(server_mod, "_device_beside_host",
                            lambda: True)
    clock = VirtualClock(0.0) if setting == "virtual_clock" else None
    storage = "tiered" if setting == "host_backed" else "device"
    with _session(storage=storage, clock=clock) as sess:
        _, flying = _serve(sess, _queries(2, 4 * B), force=False)
        assert flying == [0] * 4


class _OneUpdate:
    """An update stream holding one delta to table 0, handed out on its
    first poll; notes how many queries were in flight at each poll."""

    def __init__(self, rows, vals):
        self.rows, self.vals = rows, vals
        self.batcher = None
        self.version = None
        self.in_flight_at_poll = []

    def poll(self):
        self.in_flight_at_poll.append(self.batcher.in_flight)
        if len(self.in_flight_at_poll) > 1:
            return []
        return [{"version": self.version, "kind": "delta",
                 "tables": {0: (self.rows, self.vals)}}]


def test_the_update_barrier_serves_a_batch_in_flight_under_the_old_version(
        on_chip):
    queries = _queries(3, 3 * B)
    with _session() as ref:
        want, _ = _serve(ref, _queries(3, 3 * B), force=True)
    emb = EmbeddingStageConfig(num_tables=TABLES, rows=ROWS, dim=DIM,
                               pooling=POOL, storage="device")
    model = DLRM(DLRMConfig(dense_features=F, embedding=emb,
                            bottom_mlp=(32, DIM), top_mlp=(16, 1)))
    params = model.init(jax.random.PRNGKey(0))
    stream = _OneUpdate(np.arange(ROWS), np.full((ROWS, DIM), 3.0,
                                                 np.float32))
    with ServingSession(
            model, params, sla_ms=1e6,
            batcher=BatcherConfig(max_batch=B, max_wait_s=100.0),
            controllers=serving.configure(
                updates=serving.UpdateConfig(stream=stream))) as sess:
        old = sess.storage.version()
        stream.batcher, stream.version = sess.server.batcher, old + 1
        answers = _tap(sess)
        for q in queries[:2 * B]:
            sess.submit(q)
        # answers the first batch with the second in flight; the update
        # found then drains the second under the old tables
        assert sess.poll() == B
        assert stream.in_flight_at_poll == [B]
        assert not sess.server.batcher.queue
        assert sess.percentiles()["model_version"] == old + 1
        for q in queries[2 * B:]:
            sess.submit(q)
        sess.drain()
        versions = [sess.version_of(q.qid) for q in queries]
    assert versions == [old] * (2 * B) + [old + 1] * B
    for (qids, got), (ref_qids, ref_scores) in zip(answers[:2], want[:2]):
        assert qids == ref_qids
        np.testing.assert_array_equal(got.view(np.uint32),
                                      ref_scores.view(np.uint32))
    # the third batch saw the new tables
    assert answers[2][0] == want[2][0]
    assert not np.array_equal(answers[2][1], want[2][1])


def test_admission_counts_the_queries_in_flight(on_chip):
    with _session(max_queue=2 * B) as sess:
        for q in _queries(4, 2 * B):
            sess.submit(q)
        assert sess.poll() == B
        # B answered, B in flight: the bound has room for B more
        for q in _queries(5, B, qid0=2 * B):
            sess.submit(q)
        with pytest.raises(QueryShedError) as err:
            sess.submit(_queries(6, 1, qid0=3 * B)[0])
        assert err.value.reason == "queue_full"
        assert err.value.queue_len == 2 * B
        sess.drain()
    with _session(deadline_ms=500.0) as sess:
        for q in _queries(7, 2 * B):
            sess.submit(q)
        assert sess.poll() == B
        assert sess.server.batcher.in_flight == B
        # a batch's service takes 1 s: the batch in flight is a whole
        # batch ahead of the next query, which then waits past 500 ms
        sess.server.batcher.service_ewma_s = 1.0
        with pytest.raises(QueryShedError) as err:
            sess.submit(_queries(8, 1, qid0=2 * B)[0])
        assert err.value.reason == "deadline"
        assert err.value.queue_len == B
        sess.drain()


def test_a_poll_that_raises_gives_up_what_it_handed_out(on_chip):
    """An engine that raises while a batch is in flight: the poll
    re-raises, and the batches it held, in flight or just handed out,
    leave the queue unanswered (as a serial poll's one batch always
    did), so the server serves on and a drain ends."""
    with _session() as sess:
        answers = _tap(sess)
        for q in _queries(9, 3 * B + 2):
            sess.submit(q)
        assert sess.poll() == B
        forward = sess.server.forward

        def broken(dense, idx):
            raise RuntimeError("device lost")
        sess.server.forward = broken
        with pytest.raises(RuntimeError, match="device lost"):
            sess.poll()
        batcher = sess.server.batcher
        assert batcher.in_flight == 0 and sess.server._in_flight is None
        # the batch in flight and the one handed out went; two remain
        assert [q.qid for q in batcher.queue] == [3 * B, 3 * B + 1]
        sess.server.forward = forward
        sess.drain()
        assert not batcher.queue
    assert [batch for batch, _ in answers] == [list(range(B)),
                                               [3 * B, 3 * B + 1]]
