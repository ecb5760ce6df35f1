"""Tables of their own row counts and bags of their own sizes (MLPerf's
DLRM-DCNv2 shape) on the `device` backend: the flat bag kernel in
interpret mode against `embedding_bag_ref` table by table, the served
`dcn` model against the benchmark's plain reference
(`bench/references/dlrm_dcnv2.py`), its bfloat16 control, and a clear
refusal wherever such tables are not served."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EmbeddingBagCollection, EmbeddingStageConfig
from repro.kernels.embedding_bag import (EmbeddingBagOpts, embedding_bag_flat,
                                         embedding_bag_flat_ref,
                                         embedding_bag_ref)
from repro.kernels.embedding_bag.kernel import VMEM_BUDGET, bags_per_step
from repro.models.dlrm import DLRM, DLRMConfig
from repro.serving import BatcherConfig, ServingSession, TenantManager
from repro.serving import session as session_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# tables of 3, 10 and 1000 rows; bags of 1, 2, 7 and 100 rows
ROWS = (3, 10, 1000, 10)
BAGS = (2, 7, 100, 1)
DIM = 128


def _flat_case(batch, seed=0, rows=ROWS, bags=BAGS):
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.normal(size=(sum(rows), DIM)).astype(np.float32))
    idx = np.concatenate([rng.integers(0, r, size=(batch, n))
                          for r, n in zip(rows, bags)], axis=1)
    w = rng.random(idx.shape).astype(np.float32)
    first = tuple(int(x) for x in np.cumsum([0, *rows])[:-1])
    return table, jnp.asarray(idx, jnp.int32), jnp.asarray(w), first


def _per_table_ref(table, idx, w, first, mode, rows=ROWS, bags=BAGS):
    cols = np.cumsum([0, *bags])
    return np.stack([np.asarray(embedding_bag_ref(
        table[f:f + r], idx[:, a:b], None if w is None else w[:, a:b],
        mode=mode)) for f, r, a, b in zip(first, rows, cols[:-1], cols[1:])],
        axis=1)


@pytest.mark.parametrize("weighted,mode", [(False, "sum"), (True, "sum"),
                                           (True, "mean")])
def test_flat_kernel_matches_ref_per_table(weighted, mode):
    """One launch over all four tables; a batch of 5 padded to the step's
    4 queries. Each table's pooled rows equal its own bag reference."""
    table, idx, w, first = _flat_case(batch=5)
    w = w if weighted else None
    out = embedding_bag_flat(table, idx, w, first_rows=first, pooling=BAGS,
                             mode=mode,
                             opts=EmbeddingBagOpts(batch_block=4,
                                                   interpret=True))
    assert out.shape == (5, len(BAGS), DIM)
    want = _per_table_ref(table, idx, w, first, mode)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(embedding_bag_flat_ref(table, idx, w, first_rows=first,
                                          pooling=BAGS, mode=mode)),
        want, rtol=1e-5, atol=1e-5)


def test_flat_vmem_accounting():
    """A query's slab rows: each bag from an 8-aligned row; the output
    block holds its T pooled rows in whole tiles."""
    opts = EmbeddingBagOpts(batch_block=8)
    assert opts.vmem_bytes(BAGS, DIM) == (2 * 8 * (8 + 8 + 104 + 8)
                                          + 2 * 8 * 8) * DIM * 4
    dcn = (3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12, 100,
           27, 10, 3, 1, 1)
    assert opts.vmem_bytes(dcn, DIM) == (2 * 8 * 352 + 2 * 8 * 32) * DIM * 4
    assert opts.vmem_bytes(dcn, DIM) <= VMEM_BUDGET
    assert bags_per_step(opts, dcn, DIM) == 8


def test_row_split_partial_bags_sum_to_the_whole_table():
    """A table split row-wise over 16 chips (the cut of
    `bench/configs/dlrm-dcnv2-share16.json`): each chip pools only the ids
    in its slice, made local, and the 16 partial bags add up to the bag
    of the uncut table. Integer-valued rows keep every sum exact."""
    chips, rows, bag, batch = 16, 64, 7, 5
    rng = np.random.default_rng(4)
    table = jnp.asarray(rng.integers(-8, 8, (rows, DIM)).astype(np.float32))
    ids = rng.integers(0, rows, (batch, bag))
    share = rows // chips
    partial = []
    for c in range(chips):
        mine = ids // share == c
        # a fixed-size bag: the ids of other slices weigh nothing
        local = jnp.asarray(np.where(mine, ids - c * share, 0), jnp.int32)
        partial.append(np.asarray(embedding_bag_flat(
            table[c * share:(c + 1) * share], local,
            jnp.asarray(mine, jnp.float32), first_rows=(0,), pooling=(bag,),
            opts=EmbeddingBagOpts(batch_block=1, interpret=True)))[:, 0])
    whole = embedding_bag_ref(table, jnp.asarray(ids, jnp.int32))
    np.testing.assert_array_equal(sum(partial), np.asarray(whole))


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("rows", [ROWS, 10], ids=["uneven", "stack"])
def test_device_lookup_takes_the_flat_layout(backend, rows):
    """The `device` backend serves flat `[B, sum(L)]` indices over the
    `[sum(R), D]` table, with rows per table or one row count (which
    would make a `[T, R, D]` stack were the bags not per table)."""
    ecfg = EmbeddingStageConfig(num_tables=4, rows=rows, dim=DIM,
                                pooling=BAGS, backend=backend,
                                batch_block=4)
    ebc = EmbeddingBagCollection(ecfg)
    params = ebc.init(jax.random.PRNGKey(3))
    per_table = ecfg.table_rows()
    _, idx, _, _ = _flat_case(batch=6, rows=per_table)
    table = params["tables"]
    first = tuple(int(x) for x in np.cumsum([0, *per_table])[:-1])
    assert params["tables"].shape == (sum(per_table), DIM)
    out = ebc.apply(params, idx)
    np.testing.assert_allclose(np.asarray(out),
                               _per_table_ref(table, idx, None, first, "sum",
                                              rows=per_table),
                               rtol=1e-5, atol=1e-5)


def test_stack_refuses_flat_indices():
    """A `[T, R, D]` stack serves `[B, T, L]` indices only; bags per table
    come with the `[sum(R), D]` table that `init` draws for them."""
    ecfg = EmbeddingStageConfig(num_tables=4, rows=10, dim=DIM, pooling=3,
                                backend="xla")
    ebc = EmbeddingBagCollection(ecfg)
    params = ebc.init(jax.random.PRNGKey(5))
    assert params["tables"].shape == (4, 10, DIM)
    with pytest.raises(ValueError, match=r"takes \[B, T, L\] indices"):
        ebc.apply(params, jnp.zeros((2, 12), jnp.int32))


def test_per_table_config_layouts():
    cfg = EmbeddingStageConfig(num_tables=4, rows=ROWS, dim=DIM,
                               pooling=BAGS)
    assert cfg.per_table
    assert cfg.row_offsets() == (0, 3, 13, 1013, 1023)
    assert cfg.query_index_shape() == (110,)
    assert cfg.table_bytes() == 1023 * DIM * 4
    uniform = EmbeddingStageConfig(num_tables=4, rows=10, dim=DIM, pooling=3)
    assert not uniform.per_table
    assert uniform.query_index_shape() == (4, 3)
    with pytest.raises(ValueError, match="pooling lists 3 values for 4"):
        EmbeddingStageConfig(num_tables=4, rows=10, pooling=(1, 2, 3))
    with pytest.raises(ValueError, match="shard_pad_tables"):
        EmbeddingStageConfig(num_tables=4, rows=ROWS, pooling=2,
                             shard_pad_tables=2)


def _dlrm(storage="device", pinned=0, rows=(5, 40, 3, 300),
          pooling=(1, 2, 7, 12), backend="xla"):
    ecfg = EmbeddingStageConfig(num_tables=4, rows=rows, dim=16,
                                pooling=pooling, storage=storage,
                                pinned_rows=pinned, backend=backend)
    return DLRMConfig(dense_features=13, bottom_mlp=(32, 16),
                      top_mlp=(32, 8, 1), embedding=ecfg, interaction="dcn",
                      dcn_layers=2, dcn_rank=8)


@pytest.mark.parametrize("storage", ["tiered", "sharded", "pool"])
def test_host_backed_backends_refuse_per_table_geometry(storage):
    with pytest.raises(ValueError, match=f"storage={storage!r} serves "
                                         "tables of one row count"):
        DLRM(_dlrm(storage=storage))


def test_pinned_rows_refuse_per_table_geometry():
    with pytest.raises(ValueError, match="take pinned_rows=0"):
        DLRM(_dlrm(pinned=2))


def test_tenants_refuse_per_table_geometry():
    from repro.serving import TenantSpec
    model = DLRM(_dlrm())
    spec = TenantSpec(name="dcn", model=model, params={})
    with pytest.raises(ValueError, match="gives rows or pooling per table"):
        TenantManager._check_geometry([spec, spec])


def test_dcn_shapes_and_named_stages():
    cfg = _dlrm()
    assert cfg.interaction_dim() == 5 * 16
    model = DLRM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert {k: v.shape for k, v in params["cross"].items()} == {
        "v0": (80, 8), "u0": (8, 80), "b0": (80,),
        "v1": (80, 8), "u1": (8, 80), "b1": (80,)}
    assert params["top"]["w0"].shape == (80, 32)
    assert params["embedding"]["tables"].shape == (348, 16)
    text = jax.jit(model.forward).lower(
        params, jnp.zeros((4, 13)), jnp.zeros((4, 22), jnp.int32)
    ).as_text(debug_info=True)
    for stage in ("bottom", "embedding", "cross", "top"):
        assert f"/{stage}/" in text, stage
    with pytest.raises(ValueError, match="dcn_layers >= 1"):
        DLRMConfig(interaction="dcn", dcn_layers=0)


def _bench_cfg(cfg: DLRMConfig) -> dict:
    """The benchmark configuration of the same model (its reference reads
    this form)."""
    e = cfg.embedding
    return {"num_tables": e.num_tables, "rows": list(e.rows), "dim": e.dim,
            "pooling": list(e.pooling), "dense_features": cfg.dense_features,
            "bottom_mlp": list(cfg.bottom_mlp), "top_mlp": list(cfg.top_mlp),
            "model_args": {"dcn_layers": cfg.dcn_layers,
                           "dcn_rank": cfg.dcn_rank}}


def _served_and_reference(backend, seed=2**31 + 11, batches=3, batch=16):
    from bench.references import dlrm_dcnv2
    cfg = _dlrm(backend=backend)
    bcfg = _bench_cfg(cfg)
    rng = np.random.default_rng(seed)
    n = batches * batch
    idx = np.concatenate([rng.integers(0, r, size=(n, p))
                          for r, p in zip(cfg.embedding.rows,
                                          cfg.embedding.pooling)],
                         axis=1).astype(np.int32)
    dense = rng.normal(size=(n, 13)).astype(np.float32)
    params = dlrm_dcnv2.init_params(seed, bcfg)
    with jax.default_matmul_precision("highest"):
        with ServingSession(DLRM(cfg), params,
                            batcher=BatcherConfig(max_batch=batch)) as sess:
            served = {}
            sess.server.on_batch = lambda b, s: served.update(
                zip((q.qid for q in b), s))
            for k in range(batches):
                sess.submit_batch(dense[k * batch:(k + 1) * batch],
                                  idx[k * batch:(k + 1) * batch],
                                  qid0=k * batch)
                sess.poll()
    got = np.array([served[i] for i in range(n)])
    sel = np.arange(n)
    ref, _ = dlrm_dcnv2.reference(seed, bcfg, idx, dense, sel)
    ctl, _ = dlrm_dcnv2.reference(seed, bcfg, idx, dense, sel,
                                  dtype=jnp.bfloat16)
    return got, ref, ctl


def _gap(a, b):
    """Largest |a - b| over the RMS of b (the benchmark's `logit_gap`)."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.sqrt(np.mean(b ** 2)))


# The program and the reference do the same float32 arithmetic, matrix
# products at `highest`, and differ only in the order of their sums (bag
# additions, blocked products). Each rounding is ~6e-8 of its value; over
# sums of at most a few hundred terms the logits stay within 1e-5 of
# their RMS, thirty times under the benchmark's 3e-4 limit. The bfloat16
# control (one precision lower, ~4e-3 a rounding) lands far outside.
TOLERANCE = 1e-5


@pytest.fixture(scope="module", params=["xla", "pallas"])
def served_and_reference(request):
    """Served through the bag kernel's flat form (interpret mode on the
    CPU) and through its XLA reference."""
    return _served_and_reference(request.param)


def test_served_dcn_matches_the_reference(served_and_reference):
    got, ref, _ = served_and_reference
    assert _gap(got, ref) <= TOLERANCE


def test_bf16_control_breaks_the_tolerance(served_and_reference):
    _, ref, ctl = served_and_reference
    assert _gap(ctl, ref) > 3 * 3e-4 > TOLERANCE


def test_warmup_and_put_follow_the_flat_layout(monkeypatch):
    """The zero batch of `_warmup` is `[b, sum(L)]` for each batch shape
    b, and `serve.put` counts the row ids it copies, padding included."""
    seen = []

    class Recorder:
        def __init__(self, name, **stats):
            seen.append((name, stats))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False
    monkeypatch.setattr(session_mod, "TraceAnnotation", Recorder)
    cfg = _dlrm()
    model = DLRM(cfg)
    sess = ServingSession(model, model.init(jax.random.PRNGKey(1)),
                          batcher=BatcherConfig(max_batch=8))
    puts = [s for name, s in seen if name == "serve.put"]
    assert puts == [{"bytes": b * 13 * 4 + b * 22 * 4, "lookups": b * 22}
                    for b in (8, 4, 2, 1)]
    sess.close()


def test_device_update_addresses_rows_through_the_table_offset():
    cfg = _dlrm()
    model = DLRM(cfg)
    params = model.init(jax.random.PRNGKey(2))
    storage = model.ebc.storage.build(params)
    new = np.full((2, 16), 7.0, np.float32)
    storage.begin_update(1)
    storage.apply_update(3, np.array([0, 299]), new)
    with pytest.raises(ValueError, match=r"rows outside \[0, 3\)"):
        storage.apply_update(2, np.array([3]), new[:1])
    storage.commit_update(1)
    tables = np.asarray(params["embedding"]["tables"])
    first = cfg.embedding.row_offsets()[3]
    np.testing.assert_array_equal(tables[[first, first + 299]], new)
    assert not np.any(tables[first - 1] == 7.0)
