"""Operations and the least HBM bytes of one served DLRM batch, counted
from the configuration's shapes and the indices actually served.

The byte counts are lower bounds that no implementation can beat: each
distinct row a batch touches in a table is read once, plus the indices
read and the pooled rows written. Pinning, dedup or caching can bring
an implementation closer to them, never below, so a share of the
roofline computed from them stays under 100 %.
"""
from __future__ import annotations

import numpy as np

INDEX_BYTES = 4          # int32 row ids


def itemsize(cfg: dict) -> int:
    return np.dtype(cfg["dtype"]).itemsize


def distinct_rows(indices: np.ndarray, rows: int) -> np.ndarray:
    """indices [B, T, L] -> [T] number of distinct rows per table."""
    out = np.empty(indices.shape[1], np.int64)
    mark = np.zeros(rows, bool)
    for t in range(indices.shape[1]):
        mark[:] = False
        mark[indices[:, t].ravel()] = True
        out[t] = np.count_nonzero(mark)
    return out


def bag_bytes(cfg: dict, distinct: np.ndarray, batch: int) -> int:
    """Least bytes of one stacked bag lookup over all tables: distinct
    rows read, indices read, pooled rows written."""
    t, d, pool = cfg["num_tables"], cfg["dim"], cfg["pooling"]
    return int(np.sum(distinct) * d * itemsize(cfg)
               + t * batch * pool * INDEX_BYTES
               + t * batch * d * itemsize(cfg))


def fused_bytes(cfg: dict, hit_rows: int, launches: int, batch: int) -> int:
    """Least bytes of `launches` fused warm-cache launches (one table
    each) that between them read `hit_rows` distinct cache-resident rows:
    the slot-map read, the pooled block written, the resident rows read."""
    d, pool = cfg["dim"], cfg["pooling"]
    return int(hit_rows * d * itemsize(cfg)
               + launches * (batch * pool * INDEX_BYTES
                             + batch * d * itemsize(cfg)))


def _tower(dims: list[int]) -> list[tuple[int, int]]:
    return list(zip(dims[:-1], dims[1:]))


def top_input_dim(cfg: dict) -> int:
    f = cfg["num_tables"] + 1
    return cfg["bottom_mlp"][-1] + f * (f - 1) // 2


def mlp_shapes(cfg: dict) -> list[tuple[int, int]]:
    return (_tower([cfg["dense_features"], *cfg["bottom_mlp"]])
            + _tower([top_input_dim(cfg), *cfg["top_mlp"]]))


def step_flops(cfg: dict, batch: int) -> int:
    """Floating-point operations of one forward over `batch` queries: the
    bag additions, both MLP towers (multiply and add), and the full
    Gram matrix of the dot interaction."""
    t, d, pool = cfg["num_tables"], cfg["dim"], cfg["pooling"]
    bags = t * batch * pool * d
    mlps = sum(2 * batch * i * o for i, o in mlp_shapes(cfg))
    gram = 2 * batch * (t + 1) ** 2 * d
    return int(bags + mlps + gram)


def step_bytes(cfg: dict, distinct: np.ndarray, batch: int) -> int:
    """Least bytes of one forward: the bag lookup's, the MLP weights and
    biases, the dense features read and the logits written."""
    weights = sum(i * o + o for i, o in mlp_shapes(cfg))
    return int(bag_bytes(cfg, distinct, batch)
               + (weights + batch * cfg["dense_features"] + batch)
               * itemsize(cfg))


def least_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(seconds, bound): the larger of the compute and the memory time."""
    tc = flops / peaks["flops_per_s"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tm, "bytes") if tm >= tc else (tc, "flops")
