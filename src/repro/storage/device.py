"""`device` backend — tables fully HBM-resident, dense XLA/Pallas gather.

The seed behaviour (every table fits on device), re-homed behind the
`EmbeddingStorage` protocol. `lookup()` is the jit-traceable dense path.
For a `[T, R, D]` stack and `[B, T, L]` indices: hot-first remap,
optional zero tables appended to the stack, then either a
vmapped `jnp.take` (XLA baseline) or the Pallas slab-gather kernel's
(table, batch block) grid, and the shared pooling reduction. Rows or bag
sizes per table (`EmbeddingStageConfig.per_table`) are drawn as one
`[sum(R), D]` table and read with a first row per table, from `[B, T, L]`
or flat `[B, sum(L)]` indices, by the kernel's flat form or its XLA
reference. The table's layout decides the form, never a flag.

No staging, no refresh: with everything resident there is nothing to
overlap or re-pin at the storage level (the paper's in-kernel prefetch
lives inside the Pallas kernel itself, selected by
`EmbeddingStageConfig.backend`; `pinned_rows` stores the tables
hot-first, and the kernel fetches their rows like any others).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.embedding_bag import (embedding_bag_flat,
                                         embedding_bag_flat_ref,
                                         embedding_bag_stacked)
from repro.storage.base import EmbeddingStorage, StorageCapabilities
from repro.storage.registry import register


@register("device")
class DeviceStorage(EmbeddingStorage):
    """Dense device-resident storage: params ARE the storage.

    Online updates therefore mutate the bound params dict: `build(params)`
    binds it (same object the serving engine reads each call), and
    `commit_update` replaces `params["tables"]` with a scattered copy —
    logical row ids route through the EBC's hot-first remap, since the
    stored tables are physically permuted when `pinned_rows > 0`, and
    through the table's first row in a `[sum(R), D]` table."""

    per_table_geometry = True

    def __init__(self, ebc):
        super().__init__(ebc)
        self._params = None
        self._version = 0
        self._update_txn = None

    def capabilities(self) -> StorageCapabilities:
        return StorageCapabilities(device_resident=True, updatable=True)

    def build(self, params: dict, **kwargs) -> "DeviceStorage":
        """No materialization needed (params already ARE the storage) —
        binding the dict here is what arms online updates."""
        if kwargs:
            raise TypeError(f"backend {self.name!r} takes no build "
                            f"options, got {sorted(kwargs)}")
        # accept full-DLRM or embedding-only trees (same law as the tiered
        # _extract_tables): commit must swap "tables" inside the SUB-dict
        # the model's forward actually indexes
        if "tables" not in params and "embedding" in params:
            params = params["embedding"]
        self._params = params
        return self

    # -- online model updates -------------------------------------------------
    def version(self) -> int:
        return self._version

    def begin_update(self, version: int) -> bool:
        from repro.core.update import UpdateTxn
        if self._params is None:
            raise RuntimeError(
                "device updates mutate the bound params' tables in "
                "place — call storage.build(params) first")
        if self._update_txn is not None:
            raise RuntimeError(
                f"an update to v{self._update_txn.version} is already "
                f"open — commit or abort it first")
        self._update_txn = UpdateTxn(version, self._version)
        return True

    def apply_update(self, table: int, rows, values) -> bool:
        from repro.core.update import require_open
        cfg = self.cfg
        require_open(self._update_txn, "apply_update").add(
            table, rows, values, num_tables=cfg.num_tables,
            num_rows=cfg.table_rows(), dim=cfg.dim, dtype=cfg.jnp_dtype)
        return True

    def commit_update(self, version: int) -> dict:
        from repro.core.update import require_open
        txn = require_open(self._update_txn, "commit_update")
        txn.check_commit(version)
        merged = txn.merged()
        tables = self._params["tables"]
        applied = 0
        first = self.cfg.row_offsets()
        for t, (rows, vals) in merged.items():
            phys = (rows if self.ebc._remap is None
                    else self.ebc._remap[t][rows])
            if tables.ndim == 3:
                tables = tables.at[t, phys].set(vals)
            else:
                tables = tables.at[first[t] + phys].set(vals)
            applied += int(rows.size)
        # same dict object the engine reads per call: the swap is visible
        # on the NEXT forward, never mid-batch
        self._params["tables"] = tables
        self._version = txn.version
        self._update_txn = None
        return {"updated": True, "version": self._version,
                "rows": applied, "tables": len(merged)}

    def abort_update(self, version: int) -> bool:
        if self._update_txn is None:
            return False
        self._update_txn.check_commit(version)
        self._update_txn = None
        return True

    def lookup(self, params: dict, indices, weights=None, *,
               pre_remapped: bool = False):
        """indices: [B, T, L] or flat [B, sum(L)] int32 -> pooled
        [B, T, D] (jit-traceable)."""
        from repro.core.embedding import _pool_rows_core
        cfg = self.cfg
        tables = params["tables"]           # [T(+pad), R, D] or [sum(R), D]
        if tables.ndim == 2:
            return self._lookup_flat(tables, indices, weights)
        if indices.ndim != 3:
            raise ValueError(
                f"a [T, R, D] table stack takes [B, T, L] indices, got "
                f"shape {indices.shape}: bag sizes per table need the "
                f"[sum(R), D] table that init() draws for them")
        if not pre_remapped:
            indices = self.ebc.remap_indices(indices)
        idx_t = jnp.swapaxes(indices, 0, 1)            # [T, B, L]
        w_t = None if weights is None else jnp.swapaxes(weights, 0, 1)
        if cfg.shard_pad_tables:
            pad = jnp.zeros((cfg.shard_pad_tables, *idx_t.shape[1:]),
                            idx_t.dtype)
            idx_t = jnp.concatenate([idx_t, pad], axis=0)
            if w_t is not None:
                w_t = jnp.concatenate(
                    [w_t, jnp.zeros((cfg.shard_pad_tables, *w_t.shape[1:]),
                                    w_t.dtype)], axis=0)

        if cfg.backend == "xla" or (cfg.backend == "auto"
                                    and jax.default_backend() != "tpu"):
            rows = jax.vmap(
                lambda t, i: jnp.take(t, i, axis=0))(tables, idx_t)  # [T,B,L,D]
            pooled = _pool_rows_core(rows, w_t, cfg.combine, cfg.pooling)
        else:
            # one launch over the whole stack (off the TPU: interpret mode)
            pooled = embedding_bag_stacked(tables, idx_t, w_t,
                                           mode=cfg.combine,
                                           opts=cfg.kernel_opts())
        pooled = jnp.swapaxes(pooled, 0, 1)            # [B, T(+pad), D]
        if cfg.shard_pad_tables:
            pooled = pooled[:, :cfg.num_tables]
        return pooled

    def _lookup_flat(self, tables, indices, weights):
        """Rows or bag sizes per table: one `[sum(R), D]` table, each
        query's bags as its flat `[sum(L)]` indices."""
        cfg = self.cfg
        batch = indices.shape[0]
        pooling = cfg.table_pooling()
        first = cfg.row_offsets()[:-1]
        indices = indices.reshape(batch, -1)
        if weights is not None:
            weights = weights.reshape(batch, -1)
        if cfg.backend == "xla" or (cfg.backend == "auto"
                                    and jax.default_backend() != "tpu"):
            return embedding_bag_flat_ref(tables, indices, weights,
                                          first_rows=first, pooling=pooling,
                                          mode=cfg.combine)
        return embedding_bag_flat(tables, indices, weights, first_rows=first,
                                  pooling=pooling, mode=cfg.combine,
                                  opts=cfg.kernel_opts())
