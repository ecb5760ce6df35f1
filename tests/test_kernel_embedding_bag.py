"""Pallas embedding-bag kernel vs the pure-jnp oracle (interpret=True on CPU).

Sweeps shapes/dtypes/pipeline configs + hypothesis property tests on the
operator's algebraic invariants.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.hot_cache import plan_from_trace
from repro.kernels.embedding_bag import (EmbeddingBagOpts, embedding_bag,
                                         embedding_bag_ragged_ref,
                                         embedding_bag_ref, embedding_lookup)
from repro.kernels.embedding_bag.kernel import VMEM_BUDGET, bags_per_step

RNG = np.random.default_rng(0)


def _mk(rows, dim, batch, pooling, dtype=np.float32, seed=0):
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.normal(size=(rows, dim)).astype(dtype))
    idx = jnp.asarray(rng.integers(0, rows, size=(batch, pooling)),
                      dtype=jnp.int32)
    return table, idx


@pytest.mark.parametrize("rows,dim,batch,pooling", [
    (64, 128, 8, 4),
    (256, 128, 16, 12),
    (128, 256, 8, 7),      # pooling not a multiple of 8: slab rows padded
    (512, 64, 24, 1),      # degenerate gather (LM vocab path)
    (32, 128, 3, 5),       # batch needs padding to batch_block
    (256, 128, 8, 150),    # the served pooling: slab rows padded 150 -> 152
    (64, 128, 5, 150),     # ... with a batch padded to batch_block
    (64, 2048, 8, 200),    # slab over VMEM_BUDGET at 4 or 8 bags: 2 per
                           # step; fewer table rows than a bag's
])
@pytest.mark.parametrize("batch_block", [1, 4, 8])
def test_kernel_matches_ref_shapes(rows, dim, batch, pooling, batch_block):
    """batch_block 1 carries a one-bag slab from step to step; 8 pads
    every batch here but one or two."""
    table, idx = _mk(rows, dim, batch, pooling)
    opts = EmbeddingBagOpts(batch_block=batch_block, interpret=True)
    out = embedding_bag(table, idx, backend="pallas", opts=opts)
    ref = embedding_bag_ref(table, idx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_kernel_dtypes(dtype):
    """float32 tables match the reference; packed bf16 tables are refused
    up front (Mosaic cannot address their single rows on the TPU)."""
    table, idx = _mk(128, 128, 8, 6, dtype=np.float32)
    table = table.astype(dtype)
    opts = EmbeddingBagOpts(batch_block=4, interpret=True)
    if dtype == jnp.bfloat16:
        with pytest.raises(ValueError, match="float32 tables, got bfloat16"):
            embedding_bag(table, idx, backend="pallas", opts=opts)
        return
    out = embedding_bag(table, idx, backend="pallas", opts=opts)
    ref = embedding_bag_ref(table, idx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


def _hot_first(table, idx, num_hot):
    """The tables and indices `pinned_rows` makes: the `num_hot` most used
    rows of `idx` stored first, the indices remapped to match."""
    plan = plan_from_trace(np.asarray(idx), table.shape[0], num_hot)
    return plan.reorder_table(table), plan.remap_indices(idx)


@pytest.mark.parametrize("pooling", [6, 150])
@pytest.mark.parametrize("num_hot", [0, 1, 16, 128])
def test_kernel_hot_cache_sizes(num_hot, pooling):
    """Hot-first tables (the `pinned_rows` layout) give the same bags."""
    table, idx = _mk(128, 128, 8, pooling)
    opts = EmbeddingBagOpts(batch_block=4, interpret=True)
    out = embedding_bag(*_hot_first(table, idx, num_hot), backend="pallas",
                        opts=opts)
    ref = embedding_bag_ref(table, idx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("pooling", [6, 150])
def test_kernel_weights_and_mean(pooling):
    table, idx = _mk(128, 128, 8, pooling)
    w = jnp.asarray(RNG.random((8, pooling)).astype(np.float32))
    opts = EmbeddingBagOpts(batch_block=4, interpret=True)
    for mode in ("sum", "mean"):
        out = embedding_bag(table, idx, w, mode=mode, backend="pallas",
                            opts=opts)
        ref = embedding_bag_ref(table, idx, w, mode=mode)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pooling", [6, 150])
@pytest.mark.parametrize("batch", [1, 3, 5, 7])
def test_weighted_mean_with_batch_padding(batch, pooling):
    """`_pad_batch` coverage gap: batch % batch_block != 0 combined with
    WEIGHTED mean bags. The dummy bags carry zero weights, so their
    weighted-mean denominator hits the epsilon clamp (0/1e-9) — the padded
    rows must still slice away cleanly and the real rows must match the
    reference exactly, not just the sum path the other padding tests hit."""
    table, idx = _mk(64, 128, batch, pooling, seed=batch)
    w = jnp.asarray(np.random.default_rng(batch)
                    .random((batch, pooling)).astype(np.float32))
    opts = EmbeddingBagOpts(batch_block=4, interpret=True)
    out = embedding_bag(table, idx, w, mode="mean", backend="pallas",
                        opts=opts)
    ref = embedding_bag_ref(table, idx, w, mode="mean")
    assert out.shape == (batch, 128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_mean_no_weights():
    table, idx = _mk(64, 128, 8, 5)
    opts = EmbeddingBagOpts(batch_block=4, mode="mean", interpret=True)
    out = embedding_bag(table, idx, mode="mean", backend="pallas", opts=opts)
    ref = embedding_bag_ref(table, idx, mode="mean")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("shape", [(4, 9), (3, 5), (13,)])
def test_lookup_matches_take(shape):
    """Pooling 1: one slab row per bag; (3, 5) and (13,) pad the batch."""
    table, _ = _mk(512, 64, 1, 1)
    ids = jnp.asarray(RNG.integers(0, 512, size=shape), dtype=jnp.int32)
    opts = EmbeddingBagOpts(batch_block=4, interpret=True)
    out = embedding_lookup(table, ids, backend="pallas", opts=opts)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.take(table, ids, axis=0)),
                               rtol=1e-6)


def test_ragged_ref_matches_dense_when_uniform():
    table, idx = _mk(64, 32, 6, 4)
    flat = idx.reshape(-1)
    offsets = jnp.arange(0, 6 * 4 + 1, 4)
    ragged = embedding_bag_ragged_ref(table, flat, offsets)
    dense = embedding_bag_ref(table, idx)
    np.testing.assert_allclose(np.asarray(ragged), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Property-based invariants (hypothesis)
# ---------------------------------------------------------------------------

small = st.integers(min_value=1, max_value=16)


@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(batch=small, pooling=small, seed=st.integers(0, 2**16))
def test_prop_linearity_in_table(batch, pooling, seed):
    """bag(a*T1 + b*T2) == a*bag(T1) + b*bag(T2) for sum pooling."""
    rng = np.random.default_rng(seed)
    rows, dim = 32, 64
    t1 = jnp.asarray(rng.normal(size=(rows, dim)).astype(np.float32))
    t2 = jnp.asarray(rng.normal(size=(rows, dim)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, rows, size=(batch, pooling)),
                      dtype=jnp.int32)
    a, b = 0.7, -1.3
    lhs = embedding_bag_ref(a * t1 + b * t2, idx)
    rhs = a * embedding_bag_ref(t1, idx) + b * embedding_bag_ref(t2, idx)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**16), pooling=st.integers(2, 10))
def test_prop_bag_order_invariance(seed, pooling):
    """Sum pooling is invariant to permutation of lookups within a bag —
    checked on the PALLAS kernel (pipeline order must not leak)."""
    rng = np.random.default_rng(seed)
    rows, dim, batch = 64, 128, 4
    table = jnp.asarray(rng.normal(size=(rows, dim)).astype(np.float32))
    idx = rng.integers(0, rows, size=(batch, pooling))
    perm = rng.permutation(pooling)
    opts = EmbeddingBagOpts(batch_block=4, interpret=True)
    out1 = embedding_bag(table, jnp.asarray(idx, dtype=jnp.int32),
                         backend="pallas", opts=opts)
    out2 = embedding_bag(table, jnp.asarray(idx[:, perm], dtype=jnp.int32),
                         backend="pallas", opts=opts)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.slow
@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**16), num_hot=st.integers(0, 64))
def test_prop_hot_split_invariance(seed, num_hot):
    """Result independent of how many rows are stored hot-first."""
    rng = np.random.default_rng(seed)
    rows, dim, batch, pooling = 64, 128, 4, 5
    table = jnp.asarray(rng.normal(size=(rows, dim)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, rows, size=(batch, pooling)),
                      dtype=jnp.int32)
    base = embedding_bag_ref(table, idx)
    opts = EmbeddingBagOpts(batch_block=4, interpret=True)
    out = embedding_bag(*_hot_first(table, idx, num_hot), backend="pallas",
                        opts=opts)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base), rtol=1e-5,
                               atol=1e-5)


def test_vmem_budget_accounting():
    """Two slabs of batch_block x L_pad rows, and two pipeline buffers of
    the output block; the bags per step fall to a divisor of batch_block
    only where that would not fit."""
    opts = EmbeddingBagOpts(batch_block=8)
    assert opts.vmem_bytes(pooling=150, dim=128) == (
        2 * 8 * 152 + 2 * 8) * 128 * 4
    served = EmbeddingBagOpts()
    assert served.vmem_bytes(pooling=150, dim=128) <= VMEM_BUDGET
    assert bags_per_step(served, 150, 128) == 8
    assert bags_per_step(EmbeddingBagOpts(batch_block=4), 200, 2048) == 2
    assert bags_per_step(EmbeddingBagOpts(batch_block=6), 4000, 128) == 3
    assert bags_per_step(EmbeddingBagOpts(), 100_000, 128) == 1
