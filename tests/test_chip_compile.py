"""Compile-only checks of the serving kernels for a described TPU v5e.

Nothing runs: each test lowers and compiles one program at the paper's
widths (B=2048, L=150, D=128, R=500K rows, float32), or at MLPerf
DLRM-DCNv2's (26 tables of their own rows and bag sizes), for a v5e chip that
is described, not attached, and asserts that the Pallas kernel is in the
compiled program (`tpu_custom_call`). This catches what interpret mode
cannot — tiling alignment, SMEM/VMEM budgets, operands Mosaic will not
lower — at no chip time.

The topology is described inside a module fixture (never at import), so
every pytest-xdist worker collects the same tests and only the worker
given this file loads the TPU compiler. Code that asks
`jax.default_backend()` still sees the CPU here, so the tests steer the
kernel wrappers' platform probe to the TPU branch with `monkeypatch`.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import EmbeddingBagCollection, EmbeddingStageConfig
from repro.kernels.embedding_bag import (EmbeddingBagOpts, FusedLookupOpts,
                                         embedding_bag_pallas,
                                         fused_warm_lookup_pallas)
from repro.kernels.embedding_bag import kernel as bag_kernel
from repro.kernels.embedding_bag import ops as bag_ops

T, R, D, B, L = 2, 500_000, 128, 2048, 150
HOT = 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("pinned", [0, HOT])
def test_device_backend_lookup_compiles(one_chip, monkeypatch, pinned,
                                        weighted):
    """The `device` backend's lookup as the served path jits it: hot-first
    remap, then ONE stacked-table kernel launch over [T, R, D]. Its blocks
    (two slabs of 8 bags x 152 rows, output) stay under the kernel's VMEM
    budget, itself under v5e's 16 MiB scoped limit, with no cut to the
    bags per step."""
    monkeypatch.setattr(bag_ops, "_on_tpu", lambda: True)
    cfg = EmbeddingStageConfig(num_tables=T, rows=R, dim=D, pooling=L,
                               backend="pallas", pinned_rows=pinned)
    opts = cfg.kernel_opts()
    assert opts.vmem_bytes(L, D) <= bag_kernel.VMEM_BUDGET < 16 * 2**20
    assert bag_kernel.bags_per_step(opts, L, D) == opts.batch_block
    ebc = EmbeddingBagCollection(cfg)
    params = {"tables": _spec((T, R, D), jnp.float32, one_chip)}
    idx = _spec((B, T, L), jnp.int32, one_chip)
    w = _spec((B, T, L), jnp.float32, one_chip) if weighted else None
    text = jax.jit(ebc.apply).lower(params, idx, w).compile().as_text()
    assert "tpu_custom_call" in text
    assert "%embedding_bag." in text      # the name the device trace shows


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("num_hot", [0, HOT])
def test_fused_kernel_compiles_at_serving_batch(one_chip, num_hot, weighted):
    """The fused warm-cache kernel at the serving batch: its SMEM holds
    only per-step slot and weight blocks, never a B·L miss list."""
    cache = _spec((R, D), jnp.float32, one_chip)
    slots = _spec((B, L), jnp.int32, one_chip)
    w = _spec((B, L), jnp.float32, one_chip) if weighted else None
    hot = _spec((num_hot, D), jnp.float32, one_chip) if num_hot else None
    text = jax.jit(
        lambda c, s, w, h: fused_warm_lookup_pallas(
            c, s, w, h, opts=FusedLookupOpts())
    ).lower(cache, slots, w, hot).compile().as_text()
    assert "tpu_custom_call" in text
    assert "%fused_embedding_bag." in text


@pytest.mark.parametrize("mode", ["sum", "mean"])
def test_bag_kernel_weighted_modes_compile(one_chip, mode):
    """The weighted paths the served config does not take: the in-place
    scale of each slab row, and the weighted mean's divide."""
    tables = _spec((T, R, D), jnp.float32, one_chip)
    idx = _spec((T, B, L), jnp.int32, one_chip)
    w = _spec((T, B, L), jnp.float32, one_chip)
    opts = EmbeddingBagOpts(mode=mode)
    text = jax.jit(lambda t, i, w: embedding_bag_pallas(t, i, w, opts=opts)
                   ).lower(tables, idx, w).compile().as_text()
    assert "%embedding_bag." in text


@pytest.mark.parametrize("weighted", [False, True])
def test_bag_kernel_compiles_for_tables_shorter_than_a_bag(one_chip,
                                                           weighted):
    """Tables of 64 rows under bags of 150: what sizes a slab's waits
    must not slice the table."""
    tables = _spec((T, 64, D), jnp.float32, one_chip)
    idx = _spec((T, B, L), jnp.int32, one_chip)
    w = _spec((T, B, L), jnp.float32, one_chip) if weighted else None
    text = jax.jit(lambda t, i, w: embedding_bag_pallas(t, i, w)
                   ).lower(tables, idx, w).compile().as_text()
    assert "%embedding_bag." in text


@pytest.mark.parametrize("budget", ["kernel", "none"])
def test_bag_kernel_slab_fits_scoped_vmem(one_chip, monkeypatch, budget):
    """Bags of 4,000 rows: two slabs of 8 bags would take 31 MiB of VMEM.
    The wrapper lowers the bags per step from the shape (to 2), and the
    kernel compiles; with no budget it keeps 8 and Mosaic refuses it."""
    if budget == "none":
        monkeypatch.setattr(bag_kernel, "VMEM_BUDGET", 2**40)
    long_bags = 4000
    opts = EmbeddingBagOpts()
    tables = _spec((T, R, D), jnp.float32, one_chip)
    idx = _spec((T, B, long_bags), jnp.int32, one_chip)
    lowered = jax.jit(lambda t, i: embedding_bag_pallas(t, i, opts=opts)
                      ).lower(tables, idx)
    if budget == "none":
        with pytest.raises(Exception, match="vmem"):
            lowered.compile()
        return
    assert bag_kernel.bags_per_step(opts, long_bags, D) == 2
    assert "%embedding_bag." in lowered.compile().as_text()


# MLPerf DLRM-DCNv2 on one chip of 16 (bench/configs/dlrm-dcnv2-share16.json)
DCN_ROWS = (2_500_000, 39060, 17295, 7424, 20265, 3, 7122, 1543, 63,
            2_500_000, 3067956, 405282, 10, 2209, 11938, 155, 4, 976, 14,
            2_500_000, 2_500_000, 2_500_000, 590152, 12973, 108, 36)
DCN_BAGS = (3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12, 100,
            27, 10, 3, 1, 1)


@pytest.mark.parametrize("weighted", [False, True])
def test_flat_lookup_compiles_at_dcnv2_shapes(one_chip, monkeypatch,
                                              weighted):
    """The `device` lookup of 26 tables of their own rows (one
    `[16,684,588, 128]` table, within 0.6 % of 2**31 elements) under the
    published bag sizes, 214 ids a query in the flat layout: ONE kernel
    launch for all tables, its blocks under the VMEM budget."""
    monkeypatch.setattr(bag_ops, "_on_tpu", lambda: True)
    assert sum(DCN_ROWS) == 16_684_588 and sum(DCN_BAGS) == 214
    cfg = EmbeddingStageConfig(num_tables=26, rows=DCN_ROWS, dim=D,
                               pooling=DCN_BAGS, backend="pallas")
    opts = cfg.kernel_opts()
    assert opts.vmem_bytes(DCN_BAGS, D) <= bag_kernel.VMEM_BUDGET
    assert bag_kernel.bags_per_step(opts, DCN_BAGS, D) == opts.batch_block
    ebc = EmbeddingBagCollection(cfg)
    params = {"tables": _spec((sum(DCN_ROWS), D), jnp.float32, one_chip)}
    idx = _spec((B, 214), jnp.int32, one_chip)
    w = _spec((B, 214), jnp.float32, one_chip) if weighted else None
    lowered = jax.jit(ebc.apply).lower(params, idx, w)
    assert lowered.out_info.shape == (B, 26, D)
    text = lowered.compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "%embedding_bag." in text


def test_bag_kernel_refuses_bf16_tables(one_chip):
    """Packed bf16 rows cannot be addressed one at a time on the TPU; the
    kernel says so before Mosaic does."""
    tables = _spec((T, R, D), jnp.bfloat16, one_chip)
    idx = _spec((T, B, L), jnp.int32, one_chip)
    with pytest.raises(ValueError, match="float32 tables, got bfloat16"):
        jax.jit(lambda t, i: embedding_bag_pallas(
            t, i, opts=EmbeddingBagOpts())).lower(tables, idx)


def test_compiled_programs_fit_one_chip(one_chip, monkeypatch):
    """The served lookup's device footprint at 24 tables (the one-chip
    cut of the paper's 250): tables + indices + output under 16 GB."""
    monkeypatch.setattr(bag_ops, "_on_tpu", lambda: True)
    t24 = 24
    cfg = EmbeddingStageConfig(num_tables=t24, rows=R, dim=D, pooling=L,
                               backend="pallas")
    ebc = EmbeddingBagCollection(cfg)
    params = {"tables": _spec((t24, R, D), jnp.float32, one_chip)}
    idx = _spec((B, t24, L), jnp.int32, one_chip)
    mem = jax.jit(ebc.apply).lower(params, idx).compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9, used
    assert np.isclose(mem.argument_size_in_bytes,
                      t24 * R * D * 4 + B * t24 * L * 4, rtol=0.01)
