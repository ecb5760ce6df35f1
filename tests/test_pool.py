"""Multi-process shard pool (PR 8): worker transport + shared host tier.

Pins the acceptance contract of the `"pool"` backend:

  * the framed RPC transport moves payloads correctly (shm codec round
    trip, segment reclaim), surfaces remote exceptions as
    `RemoteCallError` without killing the transport, and turns process
    death / timeout into the typed `WorkerDeadError`;
  * lookups are bit-identical to the dense gather on every placement path
    — contiguous, balanced, replicated — unfused and fused, weighted and
    not, and identical to the thread-sharded backend in degraded mode;
  * a worker killed mid-serving is respawned from the shared host tier
    and the batch still answers bit-exactly;
  * cross-process build-before-teardown holds: a mid-migration worker
    kill rolls back to the old placement (old pool still serving), a
    failed rebuild leaves the old pool serving, a stale plan is a no-op;
  * the shared host cold tier is counted once per host — contiguous
    units and replicas are zero-copy views, so replication adds no
    resident cold bytes;
  * merged stats follow the exact sharded merge law (shared parametrized
    schema test: counters sum, `queue_depth` is a per-shard max);
  * the PR 4–6 serving loop (auto-tuned migration inside a live
    `ServingSession`) works unchanged over processes;
  * tenancy over processes: per-tenant lookups are bit-exact slices of
    the shared pool, the stats merge law extends to the tenant axis,
    pool tenancy is STATIC (attach/detach raise — rebuild instead), and
    per-tenant depth/degraded knobs survive a worker respawn.
"""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (EmbeddingBagCollection, EmbeddingStageConfig,
                        make_pattern)
from repro.models.dlrm import DLRM, DLRMConfig
from repro.ps import AutoTuneConfig, PSConfig
from repro.serving import BatcherConfig, ServingSession
from repro.storage import PoolStorage, ShardPlacement, WorkerDeadError
from repro.storage.pool.transport import (RemoteCallError, decode_payload,
                                          encode_payload, spawn_worker)

ROWS, TABLES, DIM, POOL = 256, 6, 16, 6
# heavy tables stacked at one end => the contiguous split starts lopsided
SKEWED = ("one_item", "one_item", "high_hot", "med_hot", "random", "random")


def _pats(hotness=SKEWED):
    return [make_pattern(h, ROWS, seed=t) for t, h in enumerate(hotness)]


def _batch(pats, batch, seed):
    return np.stack([p.sample(batch, POOL, seed=seed * 100 + t)
                     for t, p in enumerate(pats)], axis=1).astype(np.int32)


def _trace(pats, batches=3, batch=8, seed0=50):
    return np.concatenate([_batch(pats, batch, seed0 + s)
                           for s in range(batches)], axis=0)


def _stage_cfg(storage="device", **kw):
    return EmbeddingStageConfig(num_tables=TABLES, rows=ROWS, dim=DIM,
                                pooling=POOL, backend="xla",
                                storage=storage, **kw)


@pytest.fixture(scope="module")
def dense_ref():
    ebc = EmbeddingBagCollection(_stage_cfg("device"))
    params = ebc.init(jax.random.PRNGKey(0))
    return ebc, params


def _build_pool(params, pats, ps_cfg=None, **kw):
    ebc = EmbeddingBagCollection(_stage_cfg("pool"))
    kw.setdefault("num_workers", 2)
    if ps_cfg is None:
        ps_cfg = PSConfig(hot_rows=16, warm_slots=16, async_prefetch=True,
                          window_batches=8)
    ebc.storage.build(params, ps_cfg, trace=_trace(pats), **kw)
    return ebc


def _check(ebc, ebc0, params, pats, seed, batch=8):
    idx = _batch(pats, batch, seed=seed)
    got = np.asarray(ebc.apply(params, jnp.asarray(idx)))
    want = np.asarray(ebc0.apply(params, jnp.asarray(idx)))
    assert np.array_equal(got, want), seed


# ---------------------------------------------------------------------------
# transport: shm codec, remote errors, typed death
# ---------------------------------------------------------------------------

def test_shm_codec_round_trip():
    from repro.storage.pool.transport import (SHM_INLINE_MAX, _ShmArray,
                                              attach_segment)
    big = np.arange(SHM_INLINE_MAX, dtype=np.float32).reshape(2, -1)
    small = np.arange(8, dtype=np.int64)
    payload = {"big": big, "nest": [small, {"s": "x", "n": 3}], "t": (big,)}
    segments = []
    frame = encode_payload(payload, segments)
    # large arrays left the frame, small ones ride inline
    assert isinstance(frame["big"], _ShmArray)
    assert isinstance(frame["t"][0], _ShmArray)
    assert isinstance(frame["nest"][0], np.ndarray)
    assert len(segments) == 2
    names = [s.name for s in segments]
    out = decode_payload(frame)
    assert np.array_equal(out["big"], big)
    assert np.array_equal(out["t"][0], big)
    assert np.array_equal(out["nest"][0], small)
    assert out["nest"][1] == {"s": "x", "n": 3}
    # the receiver consumed (unlinked) the segments
    for name in names:
        with pytest.raises(FileNotFoundError):
            attach_segment(name)
    for seg in segments:
        seg.close()


def test_worker_remote_error_keeps_transport_alive():
    t = spawn_worker(0)
    try:
        info = t.ping()
        assert info["worker"] == 0 and info["units"] == []
        with pytest.raises(RemoteCallError) as ei:
            t.call("no_such_verb")
        assert ei.value.err_type == "ValueError"
        assert not t.dead                       # verb failed, worker didn't
        # construct before attach_tables is a remote error with traceback
        with pytest.raises(RemoteCallError, match="attach_tables"):
            t.call("construct", {"units": [], "ps_cfg": None})
        assert t.ping()["pid"] == t.pid
    finally:
        t.shutdown()
    assert t.dead and not t.proc.is_alive()


def test_killed_worker_raises_typed_error_and_stays_dead():
    t = spawn_worker(3)
    try:
        assert t.ping()["worker"] == 3
        t.kill()                                # SIGKILL, transport unaware
        with pytest.raises(WorkerDeadError) as ei:
            t.ping()
        assert ei.value.worker == 3
        assert t.dead
        with pytest.raises(WorkerDeadError, match="respawn"):
            t.ping()                            # dead transports stay dead
    finally:
        t.shutdown()


def test_call_timeout_marks_transport_dead():
    t = spawn_worker(0)
    try:
        assert t.ping()["worker"] == 0
        with pytest.raises(WorkerDeadError, match="timed out"):
            t.call("sleep", {"seconds": 30.0}, timeout=0.05)
        assert t.dead                           # a late reply is never read
    finally:
        t.shutdown()


# ---------------------------------------------------------------------------
# bit-exactness vs the dense gather: every placement path
# ---------------------------------------------------------------------------

def test_pool_bit_exact_and_rebuild(dense_ref):
    """Contiguous placement, then a LIVE rebuild to balanced on the same
    backend — staging and refresh interleaved, every answer bit-exact."""
    ebc0, params = dense_ref
    pats = _pats()
    ebc = _build_pool(params, pats, placement="contiguous")
    st = ebc.storage
    with st:
        caps = st.capabilities()
        assert caps.stageable and caps.async_prefetch and caps.migratable
        assert st.num_shards == 2 and st.num_workers == 2
        for seed in range(4):
            if seed == 1:       # staged payloads must not change values
                st.stage(_batch(pats, 8, seed=2))
            if seed == 3:       # neither must a mid-stream re-pin
                assert st.refresh()["replanned"]
            _check(ebc, ebc0, params, pats, seed)
        # live rebuild: balanced placement, old workers serve until the
        # new pool is fully constructed
        st.build(params, PSConfig(hot_rows=16, warm_slots=16,
                                  async_prefetch=True, window_batches=8),
                 trace=_trace(pats), num_workers=2, placement="balanced")
        assert st.placement.strategy == "balanced"
        for seed in range(4, 8):
            _check(ebc, ebc0, params, pats, seed)


def test_pool_fused_bit_exact(dense_ref):
    ebc0, params = dense_ref
    pats = _pats()
    ebc = _build_pool(params, pats,
                      ps_cfg=PSConfig(hot_rows=16, warm_slots=16,
                                      warm_backing="device",
                                      fused_lookup=True, window_batches=8))
    with ebc.storage:
        assert ebc.storage.capabilities().fused_lookup
        for seed in range(3):
            _check(ebc, ebc0, params, pats, seed)


def test_pool_refuses_device_cache_off_cpu(dense_ref, monkeypatch):
    """Workers run JAX on the host CPU, so when the parent serves on the
    chip a device-backed (or fused) pool is refused before any worker
    spawns — never quietly built on worker CPUs."""
    import importlib
    pool_mod = importlib.import_module("repro.storage.pool.pool")
    _, params = dense_ref
    monkeypatch.setattr(pool_mod, "_parent_platform", lambda: "tpu")
    monkeypatch.setattr(pool_mod, "spawn_worker", lambda *a, **k:
                        pytest.fail("a refused build spawned a worker"))
    for kw in (dict(warm_backing="device"),
               dict(warm_backing="device", fused_lookup=True)):
        ebc = EmbeddingBagCollection(_stage_cfg("pool"))
        with pytest.raises(ValueError, match="worker CPUs"):
            ebc.storage.build(params, PSConfig(hot_rows=16, warm_slots=16,
                                               **kw), num_workers=2)


def test_pool_worker_runs_jax_on_cpu(monkeypatch):
    """A spawned worker sees JAX_PLATFORMS=cpu whatever the parent's
    environment says, and the parent's environment is left as it was."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    t = spawn_worker(0)
    try:
        assert t.ping()["jax_platforms"] == "cpu"
    finally:
        t.shutdown()
    assert os.environ["JAX_PLATFORMS"] == "tpu"


def test_pool_weighted_mean_bit_exact(dense_ref):
    _, params = dense_ref
    ebc0 = EmbeddingBagCollection(_stage_cfg("device", combine="mean"))
    ebc = EmbeddingBagCollection(_stage_cfg("pool", combine="mean"))
    ebc.storage.build(params, PSConfig(hot_rows=16, warm_slots=16),
                      num_workers=2)
    with ebc.storage:
        idx = _batch(_pats(), 8, seed=0)
        w = np.random.default_rng(3).random(
            (8, TABLES, POOL)).astype(np.float32)
        got = np.asarray(ebc.apply(params, jnp.asarray(idx),
                                   jnp.asarray(w)))
        want = np.asarray(ebc0.apply(params, jnp.asarray(idx),
                                     jnp.asarray(w)))
        assert np.array_equal(got, want)


def test_pool_replicated_placement_routes_and_dedups(dense_ref):
    """A replicated table served by two worker PROCESSES: routed slices
    still partition the batch bit-exactly, and the replica's cold rows
    cost zero extra resident bytes (both copies are views of the one
    shared host segment)."""
    ebc0, params = dense_ref
    pats = _pats()
    loads = tuple(float(x) for x in np.ones(TABLES))
    plc = ShardPlacement(num_tables=TABLES, num_shards=2,
                         replicas=((0, 1), (0,), (0,), (1,), (1,), (0, 1)),
                         loads=loads)
    ebc = _build_pool(params, pats, placement=plc)
    st = ebc.storage
    with st:
        for seed in range(4):
            _check(ebc, ebc0, params, pats, seed, batch=9)  # odd batch
        routed = st.update_routing()
        assert set(routed["fractions"]) == {0, 5}
        for f in routed["fractions"].values():
            assert sum(f) == pytest.approx(1.0)
        for seed in range(4, 7):                # after a routing pass
            _check(ebc, ebc0, params, pats, seed, batch=9)
        pool_acct = st.stats()["pool"]
        tables_nbytes = TABLES * ROWS * DIM * 4
        # one shared host copy; every unit here is a contiguous run (the
        # replicas are single tables), so nothing was privately copied:
        # the replicated tables are resident ONCE, not once per worker
        assert pool_acct["shared_host_bytes"] == tables_nbytes
        assert pool_acct["private_cold_bytes"] == 0
        assert pool_acct["resident_cold_bytes"] == tables_nbytes
        # the thread-sharded equivalent would hold view-free unit copies;
        # per-worker host views over-count the shared rows instead
        assert pool_acct["host_view_bytes"] > tables_nbytes


def test_pool_worker_crash_respawns_and_stays_bit_exact(dense_ref):
    ebc0, params = dense_ref
    pats = _pats()
    ebc = _build_pool(params, pats)
    st = ebc.storage
    with st:
        _check(ebc, ebc0, params, pats, 0)
        st._transports[0].kill()                # SIGKILL mid-serving
        _check(ebc, ebc0, params, pats, 1)      # respawn + retry, exact
        status = st.worker_status()
        assert [w["alive"] for w in status] == [True, True]
        assert status[0]["units"] == [u.unit_id
                                      for u in st._worker_units[0]]
        # counters survive on the surviving worker, restart on the other
        s = st.stats()
        assert (s["hot_hits"] + s["warm_hits"] + s["cold_misses"]
                == s["total_accesses"])


# ---------------------------------------------------------------------------
# cross-process migration: bit-exact swap, killed-worker rollback
# ---------------------------------------------------------------------------

def test_pool_migration_rollback_then_success(dense_ref):
    ebc0, params = dense_ref
    pats = _pats()
    ebc = _build_pool(params, pats, placement="contiguous",
                      migration_threshold=1.1)
    st = ebc.storage
    with st:
        for seed in range(4):                   # before (fills the window)
            st.stage(_batch(pats, 8, seed=seed + 1))
            _check(ebc, ebc0, params, pats, seed)
        plan = st.plan_migration()
        assert plan is not None                 # skew crossed the threshold
        old_placement = st.placement

        # a worker killed mid-swap: phase 1 fails, pending units abort on
        # the survivor, the dead worker respawns with the OLD units
        st._transports[1].kill()
        res = st.install_migration(plan)
        assert res == {"migrated": False, "rolled_back": True,
                       "respawned_workers": [1]}
        assert st.placement is old_placement    # old pool still serving
        _check(ebc, ebc0, params, pats, 4)

        # the same plan still matches the (unchanged) placement: apply it
        res = st.install_migration(plan)
        assert res["migrated"]
        assert res["imbalance_after"] < res["imbalance_before"]
        assert st.placement.strategy == "balanced"
        for seed in range(5, 9):                # after the swap
            st.stage(_batch(pats, 8, seed=seed + 1))
            _check(ebc, ebc0, params, pats, seed)
        # a raced plan (planned against the old placement) is a no-op
        assert st.install_migration(plan) == {"migrated": False,
                                              "stale_plan": True}
        s = st.stats()
        assert (s["hot_hits"] + s["warm_hits"] + s["cold_misses"]
                == s["total_accesses"])


def test_pool_rebuild_failure_leaves_old_pool_serving(dense_ref):
    """A rebuild whose workers never come up (boot deadline exceeded)
    destroys only the NEW processes and segment — the old pool keeps
    serving bit-exactly."""
    ebc0, params = dense_ref
    pats = _pats()
    ebc = _build_pool(params, pats)
    st = ebc.storage
    with st:
        _check(ebc, ebc0, params, pats, 0)
        old_transports = list(st._transports)
        with pytest.raises(WorkerDeadError):
            st.build(params, PSConfig(hot_rows=8, warm_slots=8),
                     trace=_trace(pats), num_workers=2,
                     rpc_timeout=0.01)          # worker boot takes ~1s
        assert st._transports == old_transports
        assert st.capabilities().stageable
        assert st._timeout > 1.0                # old RPC deadline restored
        _check(ebc, ebc0, params, pats, 1)


# ---------------------------------------------------------------------------
# degraded mode across processes
# ---------------------------------------------------------------------------

def test_pool_degraded_matches_thread_sharded(dense_ref):
    """Warm-cache-only serving is deterministic given cache state, and the
    pool evolves per-unit caches exactly as the thread-sharded backend
    does (same units, same batches) — so degraded answers must MATCH the
    sharded backend bit-for-bit, and the flag must survive a respawn."""
    ebc0, params = dense_ref
    pats = _pats()
    ps_kw = dict(hot_rows=16, warm_slots=16, async_prefetch=False,
                 window_batches=8)
    ebc_s = EmbeddingBagCollection(_stage_cfg("sharded"))
    ebc_s.storage.build(params, PSConfig(**ps_kw), trace=_trace(pats),
                        num_shards=2, placement="contiguous")
    ebc_p = _build_pool(params, pats, ps_cfg=PSConfig(**ps_kw),
                        placement="contiguous")
    with ebc_s.storage, ebc_p.storage:
        for seed in range(2):                   # same warm-up traffic
            idx = jnp.asarray(_batch(pats, 8, seed=seed))
            assert np.array_equal(np.asarray(ebc_s.apply(params, idx)),
                                  np.asarray(ebc_p.apply(params, idx)))
        assert ebc_s.storage.set_degraded(True)
        assert ebc_p.storage.set_degraded(True)
        assert ebc_p.storage.degraded()
        for seed in range(2, 5):
            idx = jnp.asarray(_batch(pats, 8, seed=seed))
            assert np.array_equal(np.asarray(ebc_s.apply(params, idx)),
                                  np.asarray(ebc_p.apply(params, idx)))
        sp = ebc_p.storage.stats()
        assert sp["degraded_lookups"] >= 1 and sp["degraded_rows"] > 0
        # a respawned worker must come up in the PUBLISHED serving mode
        ebc_p.storage._transports[1].kill()
        ebc_p.apply(params, jnp.asarray(_batch(pats, 8, seed=9)))
        assert all(w["degraded"] for w in ebc_p.storage.worker_status())
        # exact serving restores bit-exactness vs dense
        assert ebc_p.storage.set_degraded(False)
        _check(ebc_p, ebc0, params, pats, 10)


# ---------------------------------------------------------------------------
# stats: the merge law is SHARED across backends (satellite c)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,build_kw", [
    ("sharded", {"num_shards": 2}),
    ("pool", {"num_workers": 2}),
])
def test_stats_merge_law_schema_across_backends(dense_ref, backend,
                                                build_kw):
    """Both fan-out backends publish the same merged-report schema under
    the same law: counter keys are per-shard SUMS, rates recompute from
    the summed counters, and queue gauges (`queue_depth`,
    `max_queue_depth`) are per-shard MAXES — a queue is a per-shard
    resource, so summing gauges would fabricate depth."""
    _, params = dense_ref
    pats = _pats()
    ebc = EmbeddingBagCollection(_stage_cfg(backend))
    ebc.storage.build(params,
                      PSConfig(hot_rows=16, warm_slots=16,
                               async_prefetch=True, window_batches=8),
                      trace=_trace(pats), **build_kw)
    with ebc.storage:
        for seed in range(3):
            ebc.storage.stage(_batch(pats, 8, seed=seed + 1))
            ebc.apply(params, jnp.asarray(_batch(pats, 8, seed=seed)))
        st = ebc.storage.stats()
        assert st["num_shards"] == 2 and len(st["per_shard"]) == 2
        assert st["total_accesses"] == 3 * 8 * TABLES * POOL
        assert (st["hot_hits"] + st["warm_hits"] + st["cold_misses"]
                == st["total_accesses"])
        assert 0.0 <= st["cache_hit_rate"] <= 1.0
        for key in ("total_accesses", "hot_hits", "warm_hits",
                    "cold_misses", "prefetch_hits", "staged_rows"):
            assert st[key] == sum(s[key] for s in st["per_shard"]), key
        for key in ("queue_depth", "max_queue_depth"):
            assert st[key] == max(s[key] for s in st["per_shard"]), key
        assert st["max_queue_depth"] >= 1       # staging actually queued
        if backend == "pool":
            assert st["pool"]["num_workers"] == 2
            assert st["pool"]["resident_cold_bytes"] \
                == st["pool"]["shared_host_bytes"] \
                + st["pool"]["private_cold_bytes"]
        ebc.storage.reset_stats()
        assert ebc.storage.stats()["total_accesses"] == 0


# ---------------------------------------------------------------------------
# lifecycle & serving-loop integration
# ---------------------------------------------------------------------------

def test_pool_lifecycle_validation(dense_ref):
    _, params = dense_ref
    ebc = EmbeddingBagCollection(_stage_cfg("pool"))
    assert isinstance(ebc.storage, PoolStorage)
    with pytest.raises(RuntimeError, match="build"):
        ebc.apply(params, jnp.asarray(_batch(_pats(), 4, seed=0)))
    with pytest.raises(ValueError, match="num_workers"):
        ebc.storage.build(params, PSConfig(hot_rows=8), num_workers=0)
    with pytest.raises(ValueError, match="num_shards"):
        ebc.storage.build(params, PSConfig(hot_rows=8), num_workers=2,
                          num_shards=0)


def test_pool_close_joins_workers_and_capabilities_drop(dense_ref):
    _, params = dense_ref
    pats = _pats()
    ebc = _build_pool(params, pats)
    st = ebc.storage
    procs = [t.proc for t in st._transports]
    seg_name = st._segment.name
    assert st.capabilities().stageable
    st.close()
    assert all(not p.is_alive() for p in procs)
    caps = st.capabilities()
    assert not (caps.stageable or caps.tunable or caps.migratable)
    with pytest.raises(RuntimeError, match="closed"):
        ebc.apply(params, jnp.asarray(_batch(pats, 4, seed=0)))
    from repro.storage.pool.transport import attach_segment
    with pytest.raises(FileNotFoundError):      # host memory reclaimed
        attach_segment(seg_name)
    st.close()                                  # idempotent


def test_pool_session_autotune_migrates(dense_ref):
    """The PR 5 serving loop — traffic, threshold crossing, live swap —
    driven end-to-end through worker processes by the auto-tuner."""
    _, params = dense_ref
    pats = _pats()
    model = DLRM(DLRMConfig(embedding=_stage_cfg("pool"),
                            bottom_mlp=(32, DIM), top_mlp=(16, 1)))
    params = model.init(jax.random.PRNGKey(0))
    model.ebc.storage.build(
        params, PSConfig(hot_rows=16, warm_slots=16, async_prefetch=True,
                         window_batches=8),
        trace=_trace(pats), num_workers=2, placement="contiguous")
    cfg = AutoTuneConfig(depth_every_batches=0, migrate_every_batches=3,
                         migrate_threshold=1.1)
    with ServingSession(model, params,
                        batcher=BatcherConfig(max_batch=8, max_wait_s=0.0),
                        sla_ms=1e6, auto_tune=cfg) as sess:
        for b in range(8):
            dense = np.zeros((8, model.cfg.dense_features), np.float32)
            sess.submit_batch(dense, _batch(pats, 8, seed=b))
            if b >= 1:
                sess.poll()
        sess.drain()
        pct = sess.percentiles()
    migs = [e for e in sess.tuner.events if e["kind"] == "migration"]
    assert len(migs) >= 1
    assert pct["migrations"] == len(migs)
    assert model.ebc.storage.placement.strategy == "balanced"
    model.ebc.storage.close()


# ---------------------------------------------------------------------------
# tenancy over processes: static namespaces, merge law, respawn re-apply
# ---------------------------------------------------------------------------

def _pool_tenants(params, **kw):
    ebc = EmbeddingBagCollection(_stage_cfg("pool"))
    kw.setdefault("num_workers", 2)
    kw.setdefault("tenants", {"a": 2, "b": 4})
    ebc.storage.build(params, PSConfig(hot_rows=32, warm_slots=16), **kw)
    return ebc.storage


def _device_slice_ref(tables, idx):
    """Dense reference over a tenant's slice of the shared tables."""
    cfg = EmbeddingStageConfig(num_tables=tables.shape[0],
                               rows=ROWS, dim=DIM, pooling=idx.shape[2],
                               storage="device")
    return np.asarray(EmbeddingBagCollection(cfg).apply(
        {"tables": tables}, idx))


def test_pool_tenants_bit_exact_and_merge_law(dense_ref):
    """Two tenants over one worker pool: per-tenant lookups bit-exact
    against the dense slice, whole-backend lookup undefined, tenant-axis
    stats merge law (counters and device bytes fold into the shared
    report), pool tenancy static (typed attach/detach errors)."""
    from repro.storage.tenancy import TenantStorage
    _, params = dense_ref
    tables = np.asarray(params["tables"])
    st = _pool_tenants(params)
    try:
        rng = np.random.default_rng(0)
        ia = rng.integers(0, ROWS, size=(8, 2, POOL)).astype(np.int32)
        ib = rng.integers(0, ROWS, size=(8, 4, 3)).astype(np.int32)
        va, vb = TenantStorage(st, "a"), TenantStorage(st, "b")
        ra = _device_slice_ref(tables[0:2], ia)
        rb = _device_slice_ref(tables[2:6], ib)   # per-tenant pooling L
        assert np.array_equal(np.asarray(va.lookup({}, ia)), ra)
        assert np.array_equal(np.asarray(vb.lookup({}, ib)), rb)
        with pytest.raises(RuntimeError, match="tenancy"):
            st.lookup({}, np.zeros((1, TABLES, POOL), np.int32))
        st_all = st.stats()
        assert set(st_all) == {"tenants", "shared"}
        ta, tb, sh = (st_all["tenants"]["a"], st_all["tenants"]["b"],
                      st_all["shared"])
        for key in ("total_accesses", "hot_hits", "warm_hits",
                    "cold_misses", "device_bytes"):
            assert ta[key] + tb[key] == sh[key], key
        assert sh["num_tenants"] == 2 and "pool" in sh
        # per-tenant runtime knobs are isolated
        assert va.set_degraded(True) and va.degraded()
        assert not vb.degraded()
        va.set_degraded(False)
        assert va.set_prefetch_depth(3)
        assert va.prefetch_depth() == 3 != vb.prefetch_depth()
        # static tenancy: rebuild, don't mutate, the namespace layout
        with pytest.raises(RuntimeError, match="static"):
            st.attach_tenant("c", tables[:1])
        with pytest.raises(RuntimeError, match="static"):
            st.detach_tenant("a")
        # tenant-scoped retune + refresh keep answers exact
        assert va.retune_capacities(2 << 20)["tenant"] == "a"
        va.lookup({}, ia)
        va.refresh()
        assert np.array_equal(np.asarray(va.lookup({}, ia)), ra)
    finally:
        st.close()


def test_pool_tenant_state_survives_worker_respawn(dense_ref):
    """A killed worker respawns with its tenant units' depth/degraded
    state re-applied — per-tenant knobs are pool state, not process
    state."""
    from repro.storage.tenancy import TenantStorage
    _, params = dense_ref
    tables = np.asarray(params["tables"])
    st = _pool_tenants(params)
    try:
        rng = np.random.default_rng(1)
        ia = rng.integers(0, ROWS, size=(8, 2, POOL)).astype(np.int32)
        va = TenantStorage(st, "a")
        ra = _device_slice_ref(tables[0:2], ia)
        assert va.set_prefetch_depth(3)
        st._transports[0].proc.kill()
        st._transports[0].proc.join()
        assert np.array_equal(np.asarray(va.lookup({}, ia)), ra)
        assert va.prefetch_depth() == 3
    finally:
        st.close()
