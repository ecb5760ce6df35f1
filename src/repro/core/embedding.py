"""EmbeddingBagCollection — the paper's embedding stage as a composable module.

Owns a stack of homogeneous embedding tables [T, R, D] (heterogeneous sets are
grouped into homogeneous collections by the DLRM model), the per-table
hot-first plans (L2P analogue), and the kernel tuning knobs. Tables are
processed with a single stacked lookup (one kernel launch over the stack, or
a vmapped gather), matching the paper's "each GPU executes one or more
embedding tables serially" — the kernel's table grid axis is the
serialization.

Storage is pluggable: `EmbeddingStageConfig.storage` names a backend in the
`repro.storage` registry (`device` — dense XLA/Pallas gather, seed
behaviour; `tiered` — the repro/ps hot/warm/cold parameter server;
`sharded` — table-wise partition of the tiered store), and `apply()`
delegates to `self.storage.lookup(...)`. All backends are bit-exact with
the dense gather; see docs/architecture.md for the layer map and
docs/serving.md for the old→new migration table.

Distribution: table-wise sharding over the `model` mesh axis (stack axis 0),
batch over `data` — the classic DLRM hybrid parallelism. The all-to-all that
moves lookup outputs from model-parallel to data-parallel layout is inserted
by XLA under jit from the in/out shardings (an explicit shard_map variant is
exercised in launch/steps.py as the optimized path).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hot_cache
from repro.kernels.embedding_bag import EmbeddingBagOpts


def _pool_rows_core(rows_t: jnp.ndarray, w_t: jnp.ndarray | None,
                    combine: str, pooling: int) -> jnp.ndarray:
    """Pool gathered rows [T, B, L, D] -> [T, B, D].

    The single reduction shared by every storage backend — all feed it
    identically-valued [T, B, L, D] rows, which is what makes `tiered` and
    `sharded` bit-identical to `device`.
    """
    if w_t is not None:
        rows_t = rows_t * w_t[..., None].astype(rows_t.dtype)
    pooled = rows_t.sum(axis=2)
    if combine == "mean":
        pooled = pooled / pooling
    return pooled


@functools.partial(jax.jit, static_argnames=("num_tables", "rows", "dim",
                                             "dtype"))
def _init_tables(rng: jax.Array, perm: jnp.ndarray | None, *,
                 num_tables: int, rows: int, dim: int, dtype) -> jnp.ndarray:
    """Random [T, R, D] tables, one table at a time (`lax.map` writes each
    into the preallocated stack), so the device peak is the stack plus
    one table — not the two or three whole stacks that an eager
    normal-scale-permute chain holds at once. `perm` [T, R] stores each
    table hot-first."""
    scale = 1.0 / np.sqrt(dim)

    def one(args):
        key, p = args
        t = jax.random.normal(key, (rows, dim), dtype) * scale
        return t if p is None else jnp.take(t, p, axis=0)
    return jax.lax.map(one, (jax.random.split(rng, num_tables), perm))


@dataclasses.dataclass(frozen=True)
class EmbeddingStageConfig:
    num_tables: int = 250          # paper §V
    rows: int = 500_000
    dim: int = 128
    pooling: int = 150
    dtype: str = "float32"         # paper: 4-byte precision
    combine: str = "sum"           # bag pooling mode
    # paper-mechanism knobs
    backend: str = "auto"          # 'xla' (baseline) | 'pallas' | 'auto'
    # Storage backend name, resolved in the repro.storage registry:
    # 'device' (tables fully HBM-resident, seed behaviour), 'tiered'
    # (repro/ps hot/warm/cold parameter server — beyond-HBM, bit-exact),
    # 'sharded' (table-wise partition of the tiered store), or any
    # backend registered out of tree.
    storage: str = "device"
    batch_block: int = 8
    # K per table stored hot-first (paper: 60K rows across L2). The bag
    # kernel fetches every row from the table, so on the `device` backend
    # this gains nothing, and remapping the indices costs time (76 ms a
    # batch at 24 tables x 500,000 rows on a v5e)
    pinned_rows: int = 0
    # pad the table stack so it divides the global device count -> each device
    # owns whole tables (table-parallel a2a plan; beyond-paper optimization,
    # see EXPERIMENTS.md SPerf iteration C1). 0 = no padding (row-wise plan).
    shard_pad_tables: int = 0

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    def table_bytes(self) -> int:
        return self.num_tables * self.rows * self.dim * self.jnp_dtype.itemsize

    def kernel_opts(self) -> EmbeddingBagOpts:
        return EmbeddingBagOpts(batch_block=self.batch_block,
                                mode=self.combine)


class EmbeddingBagCollection:
    """Functional module: init(rng) -> params; apply(params, indices) -> pooled.

    `self.storage` is the bound `repro.storage.EmbeddingStorage` backend
    (created from `cfg.storage` via the registry); host-backed backends are
    materialized with `ebc.storage.build(params, ...)` before the first
    `apply()`. (The PR 1–2 `build_parameter_server(...)` / `ps=` shims are
    gone — see the docs/serving.md migration table for the replacements.)
    """

    def __init__(self, cfg: EmbeddingStageConfig,
                 plans: Optional[list[hot_cache.HotPlan]] = None):
        self.cfg = cfg
        # Resolve the backend FIRST: unknown names and invalid
        # storage/pinned_rows combinations fail before any plan/remap
        # allocation happens. Lazy import: storage imports core.embedding.
        from repro import storage as storage_registry
        self.storage = storage_registry.create(cfg.storage, self)
        # One plan per table; identity when pinning is off.
        if plans is None:
            plans = [hot_cache.identity_plan(cfg.rows, cfg.pinned_rows)
                     for _ in range(cfg.num_tables)]
        assert len(plans) == cfg.num_tables
        self.plans = plans
        # [T, R] stacked remap, applied to raw indices before lookup.
        self._remap = (
            np.stack([p.inv_perm for p in plans]).astype(np.int32)
            if cfg.pinned_rows > 0 else None)

    # -- params -------------------------------------------------------------
    def init(self, rng: jax.Array) -> dict:
        cfg = self.cfg
        perm = None
        if cfg.pinned_rows > 0:
            # Store hot-first (offline, one-time — like the paper's pinning
            # kernel launched before the embedding bag kernel).
            perm = jnp.asarray(np.stack(
                [p.perm for p in self.plans]
                + [self.plans[0].perm] * cfg.shard_pad_tables))
        return {"tables": _init_tables(
            rng, perm, num_tables=cfg.num_tables + cfg.shard_pad_tables,
            rows=cfg.rows, dim=cfg.dim, dtype=cfg.jnp_dtype)}

    def remap_indices(self, indices: jnp.ndarray) -> jnp.ndarray:
        """Raw row ids -> hot-first ids. indices: [B, T, L]."""
        if self._remap is None:
            return indices
        remap = jnp.asarray(self._remap)  # [T, R]
        return jax.vmap(lambda r, idx: r[idx], in_axes=(0, 1), out_axes=1)(
            remap, indices)

    # -- data path ----------------------------------------------------------
    def apply(self, params: dict, indices: jnp.ndarray,
              weights: jnp.ndarray | None = None, *,
              pre_remapped: bool = False) -> jnp.ndarray:
        """indices: [B, T, L] int32 -> pooled [B, T, D].

        Thin delegation into the bound storage backend; which code path
        runs (jitted dense gather, host parameter-server lookup, sharded
        fan-out) is the backend's business."""
        return self.storage.lookup(params, indices, weights,
                                   pre_remapped=pre_remapped)
