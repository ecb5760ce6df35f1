"""core/: access patterns, hot-cache planning, embedding collection, planner."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (EmbeddingBagCollection, EmbeddingStageConfig,
                        PAPER_UNIQUE_PCT, build_plan, coverage_curve,
                        hot_coverage, make_pattern, plan_from_trace,
                        plan_embedding_stage, unique_access_pct)
from repro.core.access_patterns import (REF_ACCESSES, REF_ROWS,
                                        calibrate_alpha, expected_unique_pct)
from repro.core.hot_cache import build_plan as build_hot_plan
from repro.core.hot_cache import identity_plan, profile_counts


def test_unique_pct_calibration_hits_paper_targets():
    """Generated datasets reproduce paper Table III unique-access%% within
    a small tolerance at the reference workload size."""
    for hotness, target in PAPER_UNIQUE_PCT.items():
        if hotness in ("one_item",):
            continue
        pat = make_pattern(hotness, REF_ROWS)
        idx = pat.sample(2048, 150, seed=1)
        got = unique_access_pct(idx, REF_ROWS)
        if hotness == "random":
            # uniform sampling has its own analytic unique%% (~46%); the
            # paper's 63% comes from multi-batch averaging — we check the
            # analytic value instead.
            exp = expected_unique_pct(REF_ROWS, 0.0, REF_ACCESSES)
            assert abs(got - exp) < 2.0
        else:
            assert abs(got - target) < max(1.5, 0.15 * target), \
                (hotness, got, target)


def test_alpha_monotone_in_hotness():
    a_high = calibrate_alpha(PAPER_UNIQUE_PCT["high_hot"])
    a_med = calibrate_alpha(PAPER_UNIQUE_PCT["med_hot"])
    a_low = calibrate_alpha(PAPER_UNIQUE_PCT["low_hot"])
    assert a_high > a_med > a_low > 0


def test_one_item_and_coverage():
    pat = make_pattern("one_item", 1000)
    idx = pat.sample(16, 10)
    assert len(np.unique(idx)) == 1
    cov = coverage_curve(idx)
    assert np.isclose(cov[-1, 1], 100.0)

    hot = make_pattern("high_hot", 1000, seed=2).sample(64, 20)
    cov = coverage_curve(hot)
    # power law: first 10% of unique rows should cover well over 10% of accesses
    ten_pct = cov[np.searchsorted(cov[:, 0], 10.0), 1]
    assert ten_pct > 25.0


def test_hot_plan_roundtrip_and_determinism():
    counts = np.array([5, 0, 9, 1, 9, 3])
    plan = build_hot_plan(counts, num_hot=3)
    # hottest first; ties broken by row id
    assert list(plan.perm[:3]) == [2, 4, 0]
    # remap is a bijection
    assert sorted(plan.inv_perm) == list(range(6))
    idx = np.array([[2, 4, 0, 5]])
    remapped = plan.remap_indices(idx)
    table = np.arange(6 * 2).reshape(6, 2).astype(np.float32)
    reordered = plan.reorder_table(table)
    np.testing.assert_array_equal(reordered[remapped], table[idx])


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**16), k=st.integers(1, 32))
def test_prop_hot_plan_preserves_lookups(seed, k):
    rng = np.random.default_rng(seed)
    rows = 64
    counts = rng.integers(0, 100, rows)
    plan = build_hot_plan(counts, k)
    table = rng.normal(size=(rows, 8)).astype(np.float32)
    idx = rng.integers(0, rows, size=(5, 7))
    np.testing.assert_allclose(plan.reorder_table(table)[plan.remap_indices(idx)],
                               table[idx])


def test_hot_plan_coverage_matches_trace():
    pat = make_pattern("high_hot", 10_000, seed=3)
    trace = pat.sample(256, 50, seed=0)
    plan = plan_from_trace(trace, 10_000, num_hot=500)
    hot_rows = plan.perm[:500]
    cov = hot_coverage(trace, hot_rows)
    assert cov > 0.5  # top-500 of a high-hot trace covers most accesses


def test_planner_report():
    """The planner sizes the bag kernel's slab from the trace's pooling
    with the kernel's own VMEM model, and recommends no pinning: the
    kernel fetches every row from the table."""
    from repro.kernels.embedding_bag import kernel as bag_kernel
    pat = make_pattern("high_hot", 4096, seed=1)
    trace = pat.sample(128, 20)
    rep = plan_embedding_stage(trace, 4096, dim=128)
    assert rep.latency_bound
    assert rep.batch_block == 8 and rep.notes == ()
    assert rep.vmem_bytes == bag_kernel.EmbeddingBagOpts().vmem_bytes(20, 128)
    assert not hasattr(rep, "pinned_rows")

    flat = make_pattern("random", 4096, seed=1).sample(128, 20)
    rep2 = plan_embedding_stage(flat, 4096, dim=128)
    # a flat trace touches far more distinct rows than a hot one
    assert rep2.hotness_unique_pct > 2 * rep.hotness_unique_pct

    # bags of 4,000 rows: two slabs of 8 would overflow the budget
    long_bags = make_pattern("random", 4096, seed=1).sample(4, 4000)
    rep3 = plan_embedding_stage(long_bags, 4096, dim=128)
    assert rep3.batch_block == 2 and len(rep3.notes) == 1
    assert rep3.vmem_bytes <= bag_kernel.VMEM_BUDGET


def test_embedding_collection_pinned_equals_baseline():
    cfg0 = EmbeddingStageConfig(num_tables=4, rows=256, dim=32, pooling=6,
                                backend="xla")
    pat = make_pattern("med_hot", 256, seed=5)
    idx = np.stack([pat.sample(8, 6, seed=i) for i in range(4)], axis=1)
    ebc0 = EmbeddingBagCollection(cfg0)
    p0 = ebc0.init(jax.random.PRNGKey(0))
    base = ebc0.apply(p0, jnp.asarray(idx))

    cfgp = EmbeddingStageConfig(num_tables=4, rows=256, dim=32, pooling=6,
                                backend="pallas", pinned_rows=32,
                                batch_block=4)
    plans = [plan_from_trace(idx[:, t], 256, 32) for t in range(4)]
    ebcp = EmbeddingBagCollection(cfgp, plans)
    perm = jnp.asarray(np.stack([pl.perm for pl in plans]))
    pp = {"tables": jax.vmap(lambda t, pm: jnp.take(t, pm, axis=0))(
        p0["tables"], perm)}
    out = ebcp.apply(pp, jnp.asarray(idx))
    np.testing.assert_allclose(np.asarray(out), np.asarray(base), rtol=1e-5,
                               atol=1e-5)


def test_identity_plan():
    plan = identity_plan(10, 3)
    idx = np.array([1, 5, 9])
    np.testing.assert_array_equal(plan.remap_indices(idx), idx)
