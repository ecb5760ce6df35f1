"""The batch shapes a served batch is padded to (docs/serving.md, "Batch
shapes"): `batch_shapes` gives `max_batch` and its halvings down to
`max_batch // 8`, plus the SLO shrink rung's sizes when it is armed;
`InferenceServer._hand_out` pads a batch to the smallest shape that
holds it, never past the live `max_batch`; the session warms exactly the
server's shapes, so that no batch size compiles while serving; and a
partial batch's answers at its shape are those of the same queries
padded to `max_batch`."""
import jax
import numpy as np
import pytest

from repro.core import EmbeddingStageConfig
from repro.models.dlrm import DLRM, DLRMConfig
from repro.serving import (BatcherConfig, InferenceServer, Query, SLOConfig,
                           ServingSession)
from repro.serving import session as session_mod
from repro.serving.server import batch_shapes

ROWS, TABLES, DIM, POOL, F = 200, 3, 16, 4, 13


def _queries(seed: int, n: int) -> list:
    rng = np.random.default_rng(seed)
    dense = rng.normal(size=(n, F)).astype(np.float32)
    idx = rng.integers(0, ROWS, size=(n, TABLES, POOL)).astype(np.int32)
    return [Query(qid=i, dense=dense[i], indices=idx[i]) for i in range(n)]


def _session(max_batch: int, **kwargs) -> ServingSession:
    emb = EmbeddingStageConfig(num_tables=TABLES, rows=ROWS, dim=DIM,
                               pooling=POOL, storage="device")
    model = DLRM(DLRMConfig(dense_features=F, embedding=emb,
                            bottom_mlp=(32, DIM), top_mlp=(16, 1)))
    return ServingSession(model, model.init(jax.random.PRNGKey(0)),
                          batcher=BatcherConfig(max_batch=max_batch,
                                                max_wait_s=100.0),
                          sla_ms=1e6, **kwargs)


@pytest.mark.parametrize("max_batch, min_batch, shapes", [
    (2048, 0, (2048, 1024, 512, 256)),
    (64, 0, (64, 32, 16, 8)),
    (32, 0, (32, 16, 8, 4)),
    (100, 0, (100, 50, 25, 12)),
    (5, 0, (5, 2, 1)),
    (1, 0, (1,)),
    (64, 2, (64, 32, 16, 8, 4, 2)),
    (64, 24, (64, 32, 24, 16, 8)),
    (64, 64, (64, 32, 16, 8)),
])
def test_batch_shapes_halve_down_to_an_eighth_and_the_rungs(
        max_batch, min_batch, shapes):
    assert batch_shapes(max_batch, min_batch) == shapes


@pytest.mark.parametrize("n, padded", [
    (1, 8), (8, 8), (9, 16), (16, 16), (17, 32), (33, 64), (64, 64),
])
def test_hand_out_pads_to_the_smallest_shape_that_holds_the_batch(
        n, padded):
    srv = InferenceServer(lambda d, i: np.zeros(len(d), np.float32),
                          BatcherConfig(max_batch=64, max_wait_s=100.0))
    for q in _queries(0, n):
        srv.submit(q)
    flight = srv._hand_out(force=True)
    assert len(flight.batch) == n and flight.padded == padded


@pytest.mark.parametrize("queued, min_batch, padded", [
    (5, 24, 8), (9, 24, 16), (20, 24, 24), (48, 24, 24), (20, 0, 24),
])
def test_a_shrunken_batch_pads_within_the_live_max_batch(queued, min_batch,
                                                         padded):
    """The SLO shrink rung has lowered `max_batch` from 64 to 24: no batch
    pads past it, so a full batch runs at exactly the live `max_batch`,
    whether or not the rung's sizes are among the shapes."""
    srv = InferenceServer(lambda d, i: np.zeros(len(d), np.float32),
                          BatcherConfig(max_batch=64, max_wait_s=100.0),
                          min_batch=min_batch)
    assert srv.shapes == batch_shapes(64, min_batch)
    srv.batcher.cfg = BatcherConfig(max_batch=24, max_wait_s=100.0)
    for q in _queries(0, queued):
        srv.submit(q)
    assert srv._hand_out(force=True).padded == padded


@pytest.mark.parametrize("slo, shapes", [
    (None, (16, 8, 4, 2)),
    (SLOConfig(target_p99_ms=1e6, min_batch=1), (16, 8, 4, 2, 1)),
], ids=["ladder", "ladder_and_rungs"])
def test_warmup_compiles_exactly_the_servers_shapes(monkeypatch, slo,
                                                     shapes):
    """One zero batch per shape, each under its own `serve.put`, and one
    compiled program each."""
    warmed = []

    class Recorder:
        def __init__(self, name, **stats):
            if name == "serve.put":
                warmed.append(stats["lookups"] // (TABLES * POOL))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False
    monkeypatch.setattr(session_mod, "TraceAnnotation", Recorder)
    with _session(16, slo=slo) as sess:
        assert sess.server.shapes == shapes
        assert tuple(warmed) == shapes
        assert sess.engine_jit._cache_size() == len(shapes)


def test_batches_of_every_size_compile_nothing_after_warmup():
    with _session(16) as sess:
        compiled = sess.engine_jit._cache_size()
        queries = _queries(1, 16 * 17 // 2)
        start = 0
        for n in range(1, 17):
            for q in queries[start:start + n]:
                sess.submit(q)
            start += n
            assert sess.poll(force=True) == n
        assert sess.engine_jit._cache_size() == compiled


def test_partial_batch_answers_match_the_batch_padded_to_max_batch():
    """Five queries run at shape 8; the same queries padded to 32 through
    the same engine give the same answers, to float32 rounding (a
    smaller batch may tile the dense products differently)."""
    queries = _queries(2, 5)
    with _session(32) as sess:
        got = []
        sess.server.on_batch = lambda batch, s: got.append(np.array(s))
        for q in queries:
            sess.submit(q)
        assert sess.poll(force=True) == 5
        dense = np.zeros((32, F), np.float32)
        idx = np.zeros((32, TABLES, POOL), np.int32)
        for i, q in enumerate(queries):
            dense[i], idx[i] = q.dense, q.indices
        want = np.asarray(sess.engine_jit(sess.params, dense, idx))[:5]
    np.testing.assert_allclose(got[0], want, rtol=1e-6, atol=1e-7)
