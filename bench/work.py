"""Operations and the least HBM bytes of one served DLRM batch, counted
from the configuration's shapes and the indices actually served.

The byte counts are lower bounds that no implementation can beat: each
distinct row a batch touches in a table is read once, plus the indices
read and the pooled rows written. Pinning, dedup or caching can bring
an implementation closer to them, never below, so a share of the
roofline computed from them stays under 100 %.

A configuration's `rows` and `pooling` are one int for every table or a
list of one int per table. What depends on the model (its towers, its
interaction, its weights) is counted by the reference module that the
configuration's `reference` names, `bench/references/<reference>.py`,
as `step_flops(cfg, batch)` and `weight_count(cfg)`.
"""
from __future__ import annotations

import importlib

import numpy as np

INDEX_BYTES = 4          # int32 row ids


def itemsize(cfg: dict) -> int:
    return np.dtype(cfg["dtype"]).itemsize


def per_table(value, num_tables: int, key: str) -> list:
    """`value` of `key` for each table: one value stands for every
    table; a sequence must hold one per table."""
    if np.ndim(value) == 0:
        return [value] * num_tables
    if len(value) != num_tables:
        raise ValueError(f"{key!r} lists {len(value)} values for "
                         f"{num_tables} tables")
    return list(value)


def table_rows(cfg: dict) -> list[int]:
    return per_table(cfg["rows"], cfg["num_tables"], "rows")


def table_pooling(cfg: dict) -> list[int]:
    return per_table(cfg["pooling"], cfg["num_tables"], "pooling")


def bags(indices: np.ndarray, pooling=None) -> list[np.ndarray]:
    """Each table's [B, L_t] ids, from [B, T, L] or from the flat
    [B, sum(pooling)] layout (table t in columns off[t]:off[t+1],
    off = cumsum([0] + pooling))."""
    if indices.ndim == 3:
        return [indices[:, t] for t in range(indices.shape[1])]
    off = np.cumsum([0, *pooling])
    return [indices[:, a:b] for a, b in zip(off[:-1], off[1:])]


def distinct_rows(indices: np.ndarray, rows, pooling=None) -> np.ndarray:
    """indices of a batch in either layout -> [T] number of distinct rows
    per table. `rows` is one int or one per table; `pooling`, one per
    table, is needed only for the flat layout."""
    tables = bags(indices, pooling)
    out = np.empty(len(tables), np.int64)
    for t, r in enumerate(per_table(rows, len(tables), "rows")):
        mark = np.zeros(r, bool)
        mark[tables[t].ravel()] = True
        out[t] = np.count_nonzero(mark)
    return out


def bag_bytes(cfg: dict, distinct: np.ndarray, batch: int) -> int:
    """Least bytes of one stacked bag lookup over all tables: distinct
    rows read, indices read, pooled rows written."""
    t, d = cfg["num_tables"], cfg["dim"]
    return int(np.sum(distinct) * d * itemsize(cfg)
               + batch * sum(table_pooling(cfg)) * INDEX_BYTES
               + t * batch * d * itemsize(cfg))


def fused_bytes(cfg: dict, hit_rows: int, launches: int, batch: int) -> int:
    """Least bytes of `launches` fused warm-cache launches (one table
    each) that between them read `hit_rows` distinct cache-resident rows:
    the slot-map read, the pooled block written, the resident rows read."""
    d, pool = cfg["dim"], cfg["pooling"]
    return int(hit_rows * d * itemsize(cfg)
               + launches * (batch * pool * INDEX_BYTES
                             + batch * d * itemsize(cfg)))


def reference(cfg: dict):
    """The module of the configuration's plain reference."""
    return importlib.import_module(f"bench.references.{cfg['reference']}")


def step_flops(cfg: dict, batch: int) -> int:
    """Floating-point operations of one forward over `batch` queries, as
    the configuration's reference counts them."""
    return int(reference(cfg).step_flops(cfg, batch))


def step_bytes(cfg: dict, distinct: np.ndarray, batch: int) -> int:
    """Least bytes of one forward: the bag lookup's, the model's weights,
    the dense features read and the logits written."""
    return int(bag_bytes(cfg, distinct, batch)
               + (reference(cfg).weight_count(cfg)
                  + batch * cfg["dense_features"] + batch) * itemsize(cfg))


def least_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(seconds, bound): the larger of the compute and the memory time."""
    tc = flops / peaks["flops_per_s"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tm, "bytes") if tm >= tc else (tc, "flops")
