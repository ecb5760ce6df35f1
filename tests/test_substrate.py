"""Substrate tests: data determinism/resume, checkpoint atomicity+rotation,
fault-tolerant train loop (restart, preemption, straggler), serving batcher."""
import os
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import (CheckpointError, CheckpointManager,
                              ModelUpdateStream)
from repro.data import DLRMQueryStream, HETERO_MIXES
from repro.runtime import TrainLoop, TrainLoopConfig
from repro.serving import BatcherConfig, InferenceServer, Query


# -- data ---------------------------------------------------------------------

def test_dlrm_stream_hotness_and_mixes():
    s = DLRMQueryStream(num_tables=3, rows=1000, pooling=5, batch_size=4,
                        hotness="one_item", seed=0)
    b = s.next_batch()
    assert b.indices.shape == (4, 3, 5)
    for t in range(3):
        assert len(np.unique(b.indices[:, t])) == 1
    het = DLRMQueryStream.heterogeneous("mix1", rows=500, pooling=3,
                                        batch_size=2)
    assert het.next_batch().indices.shape[1] == sum(HETERO_MIXES["mix1"].values())


def test_dlrm_stream_resume_reproduces():
    s1 = DLRMQueryStream(num_tables=2, rows=100, pooling=4, batch_size=3,
                         seed=9)
    _ = [s1.next_batch() for _ in range(3)]
    st = s1.state_dict()
    want = s1.next_batch()
    s2 = DLRMQueryStream(num_tables=2, rows=100, pooling=4, batch_size=3,
                         seed=9)
    s2.load_state_dict(st)
    got = s2.next_batch()
    np.testing.assert_array_equal(got.indices, want.indices)
    np.testing.assert_array_equal(got.dense, want.dense)


# -- checkpoint -----------------------------------------------------------------

def test_checkpoint_roundtrip_and_rotation(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_last=2)
    tree = {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.zeros(3),
            "nested": {"x": jnp.ones((4,), jnp.int32)}}
    for step in (10, 20, 30):
        mgr.save(step, jax.tree.map(lambda x: x + step, tree),
                 extra={"stream": {"seed": 0, "step": step}})
    assert mgr.latest_step() == 30
    dirs = [d for d in os.listdir(tmp_path) if d.startswith("step_")]
    assert len(dirs) == 2  # rotation pruned step 10
    restored, extra = mgr.restore(tree)
    np.testing.assert_allclose(np.asarray(restored["w"]),
                               np.asarray(tree["w"]) + 30)
    assert extra["stream"]["step"] == 30


def test_checkpoint_ignores_partial_writes(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(5, {"w": jnp.ones(3)})
    # simulate a crashed (unpublished) save
    os.makedirs(tmp_path / ".tmp_step_000000007")
    assert mgr.latest_step() == 5


def test_checkpoint_leaf_mismatch_raises(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"w": jnp.ones(3)})
    with pytest.raises(CheckpointError):
        mgr.restore({"w": jnp.ones(3), "extra": jnp.ones(2)})


def test_checkpoint_rotate_sweeps_stale_tmp(tmp_path):
    """A crash between tmp-dir creation and the atomic rename used to leak
    `.tmp_step_*` forever — _rotate now sweeps them on the next save."""
    mgr = CheckpointManager(str(tmp_path), keep_last=2)
    os.makedirs(tmp_path / ".tmp_step_000000003")
    os.makedirs(tmp_path / ".tmp_v_000000004")
    mgr.save(5, {"w": jnp.ones(2)})
    assert [d for d in os.listdir(tmp_path)
            if d.startswith(".tmp_")] == []
    assert mgr.latest_step() == 5


# -- versioned embedding snapshots / update stream ----------------------------

def test_versioned_delta_chain_reconstructs_bit_exact(tmp_path):
    rng = np.random.default_rng(0)
    mgr = CheckpointManager(str(tmp_path))
    tables = rng.normal(size=(3, 16, 4)).astype(np.float32)
    mgr.save_version(1, tables)
    want = tables.copy()
    for v in (2, 3):
        changed = {}
        for t in range(3):
            rows = rng.choice(16, size=4, replace=False)
            vals = rng.normal(size=(4, 4)).astype(np.float32)
            changed[t] = (rows, vals)
            want[t, rows] = vals
        mgr.save_delta(v, changed)
    got = mgr.load_version(3)
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, want)
    # and the intermediate version is still materializable
    assert mgr.latest_version() == 3
    assert mgr.load_version(1).shape == tables.shape


def test_versioned_delta_edge_cases(tmp_path):
    """Empty per-table deltas are skipped; a full-table delta round-trips;
    a delta touching most rows falls back to a FULL snapshot."""
    rng = np.random.default_rng(1)
    mgr = CheckpointManager(str(tmp_path))
    tables = rng.normal(size=(2, 8, 3)).astype(np.float32)
    mgr.save_version(1, tables)
    want = tables.copy()
    full_rows = np.arange(8)
    full_vals = rng.normal(size=(8, 3)).astype(np.float32)
    want[1] = full_vals
    mgr.save_delta(2, {0: (np.array([], np.int64),
                           np.zeros((0, 3), np.float32)),
                       1: (full_rows, full_vals)})
    assert mgr.load_version_manifest(2)["kind"] == "delta"
    np.testing.assert_array_equal(mgr.load_version(2), want)
    # touching every row of every table blows the delta ratio -> full
    all_vals = rng.normal(size=(8, 3)).astype(np.float32)
    mgr.save_delta(3, {t: (full_rows, all_vals) for t in range(2)})
    assert mgr.load_version_manifest(3)["kind"] == "full"
    want[0] = all_vals
    want[1] = all_vals
    np.testing.assert_array_equal(mgr.load_version(3), want)


def test_versioned_delta_dtype_and_version_guards(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tables = np.ones((1, 4, 2), np.float32)
    mgr.save_version(1, tables)
    with pytest.raises(CheckpointError):
        mgr.save_delta(2, {0: (np.array([0]), np.ones((1, 2), np.float64))})
    with pytest.raises(CheckpointError):   # versions are monotonic
        mgr.save_version(1, tables)


def test_update_stream_polls_exactly_once(tmp_path):
    rng = np.random.default_rng(2)
    consumer = ModelUpdateStream(str(tmp_path))
    pub = ModelUpdateStream(str(tmp_path))
    tables = rng.normal(size=(2, 8, 3)).astype(np.float32)
    assert pub.version() == 0
    pub.publish_full(tables)
    pub.publish_delta({0: (np.array([1, 3]),
                           rng.normal(size=(2, 3)).astype(np.float32))})
    recs = consumer.poll()
    assert [r["version"] for r in recs] == [1, 2]
    assert recs[0]["kind"] == "full" and recs[1]["kind"] == "delta"
    # a full record normalizes to whole-table row updates
    rows, vals = recs[0]["tables"][0]
    np.testing.assert_array_equal(rows, np.arange(8))
    np.testing.assert_array_equal(vals, tables[0])
    assert consumer.poll() == []           # exactly-once per consumer
    late = ModelUpdateStream(str(tmp_path))
    assert late.poll() == []               # fresh consumers skip history
    assert late.version() == 2


# -- fault-tolerant train loop ----------------------------------------------------

class _ToyStream:
    def __init__(self):
        self.step = 0
    def next_batch(self):
        self.step += 1
        return float(self.step)
    def state_dict(self):
        return {"step": self.step}
    def load_state_dict(self, st):
        self.step = st["step"]


def _toy_step(state, batch):
    new = {"w": state["w"] + batch}
    return new, batch


def test_trainloop_checkpoints_and_restarts(tmp_path):
    cfg = TrainLoopConfig(total_steps=10, checkpoint_every=4, log_every=100)
    loop = TrainLoop(cfg, _toy_step, {"w": jnp.zeros(())}, _ToyStream(),
                     str(tmp_path))
    loop.run()
    final_w = float(loop.state["w"])

    # completion checkpoint exists; a new incarnation restores it exactly
    loop2 = TrainLoop(cfg, _toy_step, {"w": jnp.zeros(())}, _ToyStream(),
                      str(tmp_path))
    assert loop2.restore()
    assert loop2.step == 10
    loop2.run()  # nothing left to do
    assert float(loop2.state["w"]) == final_w

    # and a mid-training checkpoint restores to the right cursor
    restored, extra = loop2.ckpt.restore({"w": jnp.zeros(())}, step=8)
    assert extra["step"] == 8


def test_trainloop_retries_transient_failures(tmp_path):
    calls = {"n": 0}
    def flaky(state, batch):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("interconnect reset")
        return state, 0.0
    cfg = TrainLoopConfig(total_steps=3, checkpoint_every=100,
                          retry_backoff_s=0.0)
    loop = TrainLoop(cfg, flaky, {"w": jnp.zeros(())}, _ToyStream(),
                     str(tmp_path))
    loop.run()
    assert loop.step == 3 and calls["n"] == 4  # one retry


def test_trainloop_flags_stragglers(tmp_path):
    times = iter([0.01] * 5 + [0.2] + [0.01] * 4)
    def slow_step(state, batch):
        time.sleep(next(times))
        return state, 0.0
    cfg = TrainLoopConfig(total_steps=10, checkpoint_every=100,
                          straggler_factor=3.0)
    loop = TrainLoop(cfg, slow_step, {}, _ToyStream(), str(tmp_path))
    hist = loop.run()
    assert sum(h.straggler for h in hist) >= 1


def test_trainloop_preemption_saves(tmp_path):
    cfg = TrainLoopConfig(total_steps=100, checkpoint_every=1000)
    loop = TrainLoop(cfg, _toy_step, {"w": jnp.zeros(())}, _ToyStream(),
                     str(tmp_path))
    def step_then_preempt(state, batch):
        if loop.step == 4:
            loop._preempted = True
        return _toy_step(state, batch)
    loop.step_fn = step_then_preempt
    loop.run()
    assert loop.ckpt.latest_step() == 5  # saved on the preemption boundary


# -- serving ----------------------------------------------------------------------

def test_server_batches_and_tracks_latency():
    def forward(dense, idx):
        return dense.sum(axis=1)
    srv = InferenceServer(forward, BatcherConfig(max_batch=4, max_wait_s=0.0),
                          sla_ms=1000)
    for i in range(10):
        srv.submit(Query(qid=i, dense=np.ones(3, np.float32) * i,
                         indices=np.zeros((2, 3), np.int32)))
    srv.drain()
    assert srv.stats.served == 10
    pct = srv.stats.percentiles()
    assert pct["p99_ms"] >= pct["p50_ms"] >= 0
    assert srv.sla_violations() == 0


def test_batcher_respects_wait_window():
    from repro.serving import Batcher
    b = Batcher(BatcherConfig(max_batch=100, max_wait_s=10.0))
    b.submit(Query(qid=0, dense=np.zeros(1), indices=np.zeros((1, 1))))
    assert b.next_batch() is None  # window not elapsed, batch not full
