"""EmbeddingBagCollection — the paper's embedding stage as a composable module.

Owns the embedding tables, the per-table hot-first plans (L2P analogue),
and the kernel tuning knobs. Tables are processed with a single lookup (one
kernel launch over all tables, or a vmapped gather), matching the paper's
"each GPU executes one or more embedding tables serially".

Layouts (docs/architecture.md, "Table and index layouts"):

* `rows` and `pooling` one int each: the tables are one `[T, R, D]`
  stack. Either a tuple of one value per table (`per_table`): one
  `[sum(rows), D]` array, table t in rows
  `row_offsets()[t]:row_offsets()[t + 1]`.
* `pooling` one int: a query's indices are `[T, L]`.
  `pooling` a tuple of one bag size per table: a query's indices are the
  flat `[sum(pooling)]`, table t's bag in columns
  `sum(pooling[:t]):sum(pooling[:t + 1])`; no bag is padded.

Storage is pluggable: `EmbeddingStageConfig.storage` names a backend in the
`repro.storage` registry (`device` — dense XLA/Pallas gather, seed
behaviour; `tiered` — the repro/ps hot/warm/cold parameter server;
`sharded` — table-wise partition of the tiered store), and `apply()`
delegates to `self.storage.lookup(...)`. All backends are bit-exact with
the dense gather; see docs/architecture.md for the layer map and
docs/serving.md for the old→new migration table.

Distribution: the `device` backend serves one chip and uses no mesh; every
table it holds lives whole on that chip. A deployment larger than one chip
is served as one chip's share of its tables (`shard_pad_tables` pads that
share's stack with zero tables, which every lookup then also reads). The
`sharded` and `pool` backends split tables over host threads and
processes, not over chips.
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


#: most table rows `_init_flat_tables` draws at once
_DRAW_ROWS = 1 << 19


@functools.partial(jax.jit, static_argnames=("rows", "dim", "dtype"))
def _init_flat_tables(rng: jax.Array, *, rows: tuple, dim: int,
                      dtype) -> jnp.ndarray:
    """Random `[sum(rows), D]` tables, drawn in chunks of at most
    `_DRAW_ROWS` rows (a table's last chunk ends at its end, over rows of
    the one before) and written in place into the one array by a loop, so
    the device peak is the array plus one chunk."""
    scale = 1.0 / np.sqrt(dim)

    def draw(key, n):
        return jax.random.normal(key, (n, dim), dtype) * scale
    out = jnp.zeros((sum(rows), dim), dtype)
    for t, (off, r) in enumerate(zip(_offsets(rows), rows)):
        key = jax.random.fold_in(rng, t)
        if r <= _DRAW_ROWS:
            out = jax.lax.dynamic_update_slice(out, draw(key, r), (off, 0))
            continue

        def chunk(c, out, key=key, off=off, r=r):
            lo = jnp.minimum(c * _DRAW_ROWS, r - _DRAW_ROWS)
            return jax.lax.dynamic_update_slice(
                out, draw(jax.random.fold_in(key, c), _DRAW_ROWS),
                (off + lo, 0))
        out = jax.lax.fori_loop(0, -(-r // _DRAW_ROWS), chunk, out)
    return out


def _offsets(sizes) -> tuple:
    """(0, s0, s0 + s1, ..., sum(sizes))."""
    return tuple(int(x) for x in np.cumsum([0, *sizes]))


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
    # one count for every table, or a tuple of one per table
    rows: int | tuple[int, ...] = 500_000
    dim: int = 128
    # one bag size for every table, or a tuple of one per table
    pooling: int | tuple[int, ...] = 150
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
    # zero tables appended to the stack, so that a deployment's table count
    # divides its chip count; every lookup also reads them. 0 = no padding
    shard_pad_tables: int = 0

    def __post_init__(self):
        for key in ("rows", "pooling"):
            value = getattr(self, key)
            if isinstance(value, tuple) and len(value) != self.num_tables:
                raise ValueError(f"{key} lists {len(value)} values for "
                                 f"{self.num_tables} tables")
        if self.per_table and self.shard_pad_tables:
            raise ValueError("shard_pad_tables pads a [T, R, D] stack; "
                             "tables given per table (rows or pooling a "
                             "tuple) cannot be padded")

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def per_table(self) -> bool:
        """Rows or bag sizes given one per table: the flat table and
        index layouts, which only the `device` backend serves."""
        return isinstance(self.rows, tuple) or isinstance(self.pooling,
                                                          tuple)

    def table_rows(self) -> tuple:
        if isinstance(self.rows, tuple):
            return self.rows
        return (self.rows,) * self.num_tables

    def table_pooling(self) -> tuple:
        if isinstance(self.pooling, tuple):
            return self.pooling
        return (self.pooling,) * self.num_tables

    def row_offsets(self) -> tuple:
        """First row of each table in the flat table, and the total."""
        return _offsets(self.table_rows())

    def query_index_shape(self) -> tuple:
        """One query's indices: `[sum(pooling)]` when bag sizes are given
        per table, else `[T, L]`."""
        if isinstance(self.pooling, tuple):
            return (sum(self.pooling),)
        return (self.num_tables, self.pooling)

    def table_bytes(self) -> int:
        return sum(self.table_rows()) * self.dim * self.jnp_dtype.itemsize

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
        if cfg.per_table and cfg.pinned_rows > 0:
            raise ValueError(
                "pinned_rows stores each table of a [T, R, D] stack "
                "hot-first; tables given per table (rows or pooling a "
                "tuple) take pinned_rows=0")
        # One plan per table; identity when pinning is off.
        if plans is None:
            plans = [hot_cache.identity_plan(r, cfg.pinned_rows)
                     for r in cfg.table_rows()]
        assert len(plans) == cfg.num_tables
        self.plans = plans
        # [T, R] stacked remap, applied to raw indices before lookup.
        self._remap = (
            np.stack([p.inv_perm for p in plans]).astype(np.int32)
            if cfg.pinned_rows > 0 else None)

    # -- params -------------------------------------------------------------
    def init(self, rng: jax.Array) -> dict:
        cfg = self.cfg
        if cfg.per_table:
            return {"tables": _init_flat_tables(
                rng, rows=cfg.table_rows(), dim=cfg.dim,
                dtype=cfg.jnp_dtype)}
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
        """indices: [B, T, L] or flat [B, sum(pooling)] int32 -> pooled
        [B, T, D].

        Thin delegation into the bound storage backend; which code path
        runs (jitted dense gather, host parameter-server lookup, sharded
        fan-out) is the backend's business."""
        return self.storage.lookup(params, indices, weights,
                                   pre_remapped=pre_remapped)
