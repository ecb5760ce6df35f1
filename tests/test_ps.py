"""Tiered embedding parameter server (repro/ps) + serving integration.

Covers the acceptance contract: tiered lookup is bit-exact vs the dense
`jnp.take` path, eviction respects capacity, refresh re-plans from a new
trace window, stats counters sum to total lookups, a med_hot trace reaches
>= 80% hot+warm hit rate at <= 20% tier capacity, and the Batcher drain
starvation fix.
"""
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (EmbeddingBagCollection, EmbeddingStageConfig,
                        make_pattern)
from repro.data import DLRMQueryStream
from repro.models.dlrm import DLRM, DLRMConfig
from repro.ps import ParameterServer, PSConfig, PrefetchQueue, WarmCache
from repro.ps.prefetch import StagedBatch
from repro.serving import Batcher, BatcherConfig, InferenceServer, Query

ROWS, TABLES, DIM, POOL = 256, 4, 32, 6


def _tables(rng_seed=0):
    rng = np.random.default_rng(rng_seed)
    return rng.normal(size=(TABLES, ROWS, DIM)).astype(np.float32)


def _batch(pats, batch, pooling, seed):
    return np.stack([p.sample(batch, pooling, seed=seed * 100 + t)
                     for t, p in enumerate(pats)], axis=1).astype(np.int32)


def _med_pats(rows=ROWS):
    return [make_pattern("med_hot", rows, seed=t) for t in range(TABLES)]


# ---------------------------------------------------------------------------
# bit-exactness
# ---------------------------------------------------------------------------

def test_tiered_bit_exact_vs_device():
    cfg0 = EmbeddingStageConfig(num_tables=TABLES, rows=ROWS, dim=DIM,
                                pooling=POOL, backend="xla")
    ebc0 = EmbeddingBagCollection(cfg0)
    params = ebc0.init(jax.random.PRNGKey(0))
    pats = _med_pats()
    idx = _batch(pats, 8, POOL, seed=0)
    base = np.asarray(ebc0.apply(params, jnp.asarray(idx)))

    cfgt = EmbeddingStageConfig(num_tables=TABLES, rows=ROWS, dim=DIM,
                                pooling=POOL, storage="tiered")
    ebct = EmbeddingBagCollection(cfgt)
    ebct.storage.build(params, PSConfig(hot_rows=32, warm_slots=32),
                       trace=idx)
    out = np.asarray(ebct.apply(params, jnp.asarray(idx)))
    assert np.array_equal(out, base)  # bit-identical, not just close

    # stays exact across further batches (warm churn + prefetch + refresh)
    for seed in range(1, 6):
        idx = _batch(pats, 8, POOL, seed=seed)
        if seed == 2:
            ebct.storage.ps.stage(_batch(pats, 8, POOL, seed=3))
        if seed == 4:
            ebct.storage.ps.refresh()
        out = np.asarray(ebct.apply(params, jnp.asarray(idx)))
        base = np.asarray(ebc0.apply(params, jnp.asarray(idx)))
        assert np.array_equal(out, base)


def test_tiered_bit_exact_weighted_mean():
    cfg0 = EmbeddingStageConfig(num_tables=TABLES, rows=ROWS, dim=DIM,
                                pooling=POOL, backend="xla", combine="mean")
    ebc0 = EmbeddingBagCollection(cfg0)
    params = ebc0.init(jax.random.PRNGKey(1))
    idx = _batch(_med_pats(), 8, POOL, seed=0)
    w = np.random.default_rng(3).random((8, TABLES, POOL)).astype(np.float32)
    base = np.asarray(ebc0.apply(params, jnp.asarray(idx), jnp.asarray(w)))

    cfgt = EmbeddingStageConfig(num_tables=TABLES, rows=ROWS, dim=DIM,
                                pooling=POOL, storage="tiered",
                                combine="mean")
    ebct = EmbeddingBagCollection(cfgt)
    ebct.storage.build(params, PSConfig(hot_rows=16, warm_slots=16))
    out = np.asarray(ebct.apply(params, jnp.asarray(idx), jnp.asarray(w)))
    assert np.array_equal(out, base)


def test_tiered_requires_ps_and_rejects_double_remap():
    cfgt = EmbeddingStageConfig(num_tables=TABLES, rows=ROWS, dim=DIM,
                                pooling=POOL, storage="tiered")
    ebc = EmbeddingBagCollection(cfgt)
    with pytest.raises(RuntimeError, match="ParameterServer"):
        ebc.apply({"tables": None}, jnp.zeros((2, TABLES, POOL), jnp.int32))
    with pytest.raises(ValueError, match="pinned_rows"):
        EmbeddingBagCollection(EmbeddingStageConfig(
            num_tables=TABLES, rows=ROWS, dim=DIM, pooling=POOL,
            storage="tiered", pinned_rows=8))
    with pytest.raises(ValueError, match="storage"):
        EmbeddingBagCollection(EmbeddingStageConfig(storage="floppy"))


# ---------------------------------------------------------------------------
# tiers
# ---------------------------------------------------------------------------

def test_eviction_respects_capacity():
    pats = _med_pats()
    for policy in ("lfu", "lru"):
        ps = ParameterServer(_tables(), PSConfig(hot_rows=16, warm_slots=24,
                                                 eviction=policy))
        for seed in range(8):
            ps.lookup(_batch(pats, 16, POOL, seed=seed))
        st = ps.stats()
        assert st["evictions"] > 0          # churn actually happened
        for w in ps.warm:
            assert len(w) <= w.capacity
            assert (w.slot_row >= 0).sum() == len(w.loc)
            # tag store consistent: every loc entry points at its row
            for r, s in w.loc.items():
                assert w.slot_row[s] == r


def test_warm_cache_lfu_evicts_least_frequent():
    c = WarmCache(2, 4, "lfu")
    c.admit(np.array([10, 20]), np.ones((2, 4), np.float32),
            np.array([5, 1]))
    # row 20 (freq 1) is the victim when 30 arrives
    c.admit(np.array([30]), np.zeros((1, 4), np.float32), np.array([2]))
    assert set(c.loc) == {10, 30}
    assert c.evictions == 1


def test_warm_cache_lru_evicts_least_recent():
    c = WarmCache(2, 4, "lru")
    c.admit(np.array([1]), np.ones((1, 4), np.float32), np.array([9]))
    c.admit(np.array([2]), np.ones((1, 4), np.float32), np.array([1]))
    c.touch(c.probe(np.array([1])), np.array([1]))   # row 1 now most recent
    c.admit(np.array([3]), np.ones((1, 4), np.float32), np.array([1]))
    assert set(c.loc) == {1, 3}                      # row 2 evicted


def test_stats_counters_sum_to_total():
    pats = _med_pats()
    ps = ParameterServer(_tables(), PSConfig(hot_rows=32, warm_slots=32))
    for seed in range(6):
        ps.lookup(_batch(pats, 16, POOL, seed=seed))
    st = ps.stats()
    assert st["total_accesses"] == 6 * 16 * TABLES * POOL
    assert (st["hot_hits"] + st["warm_hits"] + st["cold_misses"]
            == st["total_accesses"])
    assert 0.0 <= st["cache_hit_rate"] <= 1.0


def test_refresh_replans_from_new_trace_window():
    pats = _med_pats()
    # identity plans: hot tier pins rows [0, K) — wrong for scattered traffic
    ps = ParameterServer(_tables(), PSConfig(hot_rows=48, warm_slots=0,
                                             window_batches=4))
    old_hot = ps.plans[0].perm[:48].copy()
    for seed in range(4):
        ps.lookup(_batch(pats, 32, POOL, seed=seed))
    cold_rate = ps.stats()["hot_hit_rate"]
    assert ps.refresh()["replanned"]
    assert not np.array_equal(ps.plans[0].perm[:48], old_hot)
    ps.reset_stats()
    for seed in range(4, 8):
        ps.lookup(_batch(pats, 32, POOL, seed=seed))
    hot_rate = ps.stats()["hot_hit_rate"]
    assert hot_rate > cold_rate + 0.2   # re-pinning recovered the hot set
    assert ps.refreshes == 1


def test_prefetch_queue_stage_consume():
    pats = _med_pats()
    ps = ParameterServer(_tables(), PSConfig(hot_rows=16, warm_slots=16,
                                             prefetch_depth=1))
    nxt = _batch(pats, 8, POOL, seed=1)
    assert ps.stage(nxt)
    assert not ps.stage(_batch(pats, 8, POOL, seed=2))   # queue full
    ps.lookup(nxt)
    st = ps.stats()
    assert st["prefetch_hits"] > 0
    assert st["queue_depth"] == 0
    # staged rows were gathered at stage time, not at lookup time
    assert st["staged_rows"] >= st["prefetch_hits"]


def test_prefetch_split_misses_partitions_exactly():
    q = PrefetchQueue(depth=2)
    staged = StagedBatch(
        indices=np.zeros((1, 1, 1), np.int32),
        rows={0: np.array([2, 5, 9])},
        data={0: np.arange(12, dtype=np.float32).reshape(3, 4)})
    hit_rows, hit_data, residual = q.split_misses(staged, 0,
                                                  np.array([2, 7, 9]))
    np.testing.assert_array_equal(hit_rows, [2, 9])
    np.testing.assert_array_equal(hit_data,
                                  staged.data[0][[0, 2]])
    np.testing.assert_array_equal(residual, [7])
    assert q.prefetch_hits == 2 and q.prefetch_misses == 1


def test_ps_config_validation():
    with pytest.raises(ValueError, match="eviction"):
        PSConfig(eviction="fifo")
    with pytest.raises(ValueError, match="capacities"):
        PSConfig(hot_rows=-1)
    assert PSConfig(hot_rows=10, warm_slots=6).capacity_rows() == 16


# ---------------------------------------------------------------------------
# acceptance benchmark: med_hot, capacity <= 20% of rows, hit rate >= 80%
# ---------------------------------------------------------------------------

def test_hit_rate_med_hot_at_20pct_capacity():
    rows, batch, pooling = 2000, 256, 20
    pats = [make_pattern("med_hot", rows, seed=t) for t in range(TABLES)]
    tables = np.zeros((TABLES, rows, 8), np.float32)
    cfg = PSConfig(hot_rows=200, warm_slots=200)      # 400/2000 = 20%
    trace = np.concatenate(
        [_batch(pats, batch, pooling, seed=s) for s in range(3)], axis=0)
    ps = ParameterServer(tables, cfg, trace=trace)
    for seed in range(3, 6):                          # warm the cache
        ps.lookup(_batch(pats, batch, pooling, seed=seed))
    ps.reset_stats()
    for seed in range(6, 12):                         # measured window
        ps.lookup(_batch(pats, batch, pooling, seed=seed))
    st = ps.stats()
    assert st["cache_hit_rate"] >= 0.80, st
    assert (st["hot_hits"] + st["warm_hits"] + st["cold_misses"]
            == st["total_accesses"])


# ---------------------------------------------------------------------------
# serving integration
# ---------------------------------------------------------------------------

def test_batcher_drain_force_flushes_partial_batch():
    """Regression: a sub-max_batch remainder with a long batching window
    must not starve/busy-spin in drain()."""
    served = []

    def fwd(dense, idx):
        served.append(len(dense))
        return np.zeros(len(dense), np.float32)

    srv = InferenceServer(fwd, BatcherConfig(max_batch=8, max_wait_s=60.0),
                          sla_ms=1e6)
    for q in range(3):
        srv.submit(Query(qid=q, dense=np.zeros(4, np.float32),
                         indices=np.zeros((TABLES, POOL), np.int32)))
    t0 = time.perf_counter()
    srv.drain(timeout_s=0.2)
    assert srv.stats.served == 3
    assert time.perf_counter() - t0 < 5.0     # no 60s window wait
    assert not srv.batcher.queue


def test_padded_partial_batch_not_counted_as_traffic():
    """Batcher zero-padding must not inflate PS stats or the refresh
    window (the padded rows still get served values — shape stability).
    Three queries pad to 4, the smallest of max_batch 8's shapes that
    holds them."""
    pats = _med_pats()
    ps = ParameterServer(_tables(), PSConfig(hot_rows=16, warm_slots=16))

    def fwd(dense, idx):
        rows = ps.lookup(idx)
        assert rows.shape == (4, TABLES, POOL, DIM)   # padded shape served
        return np.zeros(len(dense), np.float32)

    from repro.storage.tiered import TieredStorage
    srv = InferenceServer(fwd, BatcherConfig(max_batch=8, max_wait_s=0.0),
                          sla_ms=1e6, storage=TieredStorage.adopt(ps))
    idx = _batch(pats, 3, POOL, seed=0)
    for q in range(3):
        srv.submit(Query(qid=q, dense=np.zeros(4, np.float32),
                         indices=idx[q]))
    srv.drain(timeout_s=1.0)
    assert srv.stats.served == 3
    st = ps.stats()
    assert st["total_accesses"] == 3 * TABLES * POOL   # not 4 * T * L
    assert ps.window[-1].shape[0] == 3                 # window holds real n
    assert (st["hot_hits"] + st["warm_hits"] + st["cold_misses"]
            == st["total_accesses"])


def test_flush_drops_warm_and_window_but_keeps_stats():
    pats = _med_pats()
    ps = ParameterServer(_tables(), PSConfig(hot_rows=16, warm_slots=16))
    ps.lookup(_batch(pats, 8, POOL, seed=0))
    assert sum(len(w) for w in ps.warm) > 0 and len(ps.window) == 1
    total = ps.stats()["total_accesses"]
    ps.flush()
    assert sum(len(w) for w in ps.warm) == 0
    assert len(ps.window) == 0
    assert ps.stats()["total_accesses"] == total       # counters untouched


def test_stage_skips_gathers_when_queue_full():
    pats = _med_pats()
    ps = ParameterServer(_tables(), PSConfig(hot_rows=16, warm_slots=16,
                                             prefetch_depth=1))
    assert ps.stage(_batch(pats, 8, POOL, seed=1))
    gathered = ps.cold.gathered_rows
    assert not ps.stage(_batch(pats, 8, POOL, seed=2))
    assert ps.cold.gathered_rows == gathered   # no wasted cold gathers


def test_batcher_next_batch_force():
    b = Batcher(BatcherConfig(max_batch=4, max_wait_s=60.0))
    b.submit(Query(qid=0, dense=np.zeros(1), indices=np.zeros((1, 1))))
    assert b.next_batch() is None             # window open, batch partial
    out = b.next_batch(force=True)
    assert out is not None and len(out) == 1


def test_serving_tiered_end_to_end_stats_and_refresh():
    emb = EmbeddingStageConfig(num_tables=TABLES, rows=ROWS, dim=DIM,
                               pooling=POOL, storage="tiered")
    model = DLRM(DLRMConfig(embedding=emb, bottom_mlp=(64, DIM),
                            top_mlp=(32, 1)))
    params = model.init(jax.random.PRNGKey(0))
    stream = DLRMQueryStream(num_tables=TABLES, rows=ROWS, pooling=POOL,
                             batch_size=8, hotness="med_hot", seed=1)
    model.ebc.storage.build(
        params, PSConfig(hot_rows=32, warm_slots=32, window_batches=4),
        trace=stream.sample_trace(2))
    ps = model.ebc.storage.ps
    rest = jax.jit(lambda d, p: model.forward_from_pooled(params, d, p))

    def fwd(dense, idx):
        pooled = model.ebc.apply(params, idx)     # host PS + jitted pool
        return rest(jnp.asarray(dense), pooled)

    srv = InferenceServer(fwd, BatcherConfig(max_batch=8, max_wait_s=0.0),
                          sla_ms=1e6, storage=model.ebc.storage,
                          refresh_every_batches=2)
    for _ in range(4):
        b = stream.next_batch()
        for i in range(8):
            srv.submit(Query(qid=i, dense=b.dense[i], indices=b.indices[i]))
        srv.poll()
    srv.drain()
    pct = srv.stats.percentiles()
    assert pct["served"] == 32
    # cache statistics surfaced through ServeStats.percentiles()
    for key in ("hot_hit_rate", "warm_hit_rate", "cache_hit_rate",
                "cold_misses", "evictions", "refreshes"):
        assert key in pct, pct
    assert pct["refreshes"] >= 1              # periodic re-pinning ran
    # dense-path reference: identical scores for the same queries
    emb0 = EmbeddingStageConfig(num_tables=TABLES, rows=ROWS, dim=DIM,
                                pooling=POOL, backend="xla")
    model0 = DLRM(DLRMConfig(embedding=emb0, bottom_mlp=(64, DIM),
                             top_mlp=(32, 1)))
    stream0 = DLRMQueryStream(num_tables=TABLES, rows=ROWS, pooling=POOL,
                              batch_size=8, hotness="med_hot", seed=1)
    b0 = stream0.next_batch()
    want = model0.forward(params, jnp.asarray(b0.dense),
                          jnp.asarray(b0.indices))
    got = fwd(b0.dense, b0.indices)
    # scores agree to float32 noise (MLP halves run under different jit
    # fusions; the embedding stage itself is bit-exact — see tests above)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
