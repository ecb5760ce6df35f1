"""Pure-jnp oracle for the embedding-bag gather-reduce (paper Algorithm 1).

This is the semantic ground truth against which the Pallas kernel is verified
(tests sweep shapes/dtypes and assert_allclose against these functions).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import ops as jops


def embedding_bag_ref(table: jnp.ndarray, indices: jnp.ndarray,
                      weights: jnp.ndarray | None = None,
                      mode: str = "sum") -> jnp.ndarray:
    """Fixed-pooling embedding bag.

    table:   [R, D] float
    indices: [B, L] int
    weights: [B, L] float or None (per-lookup scale; also used as mask)
    returns: [B, D] (sum or mean over L)
    """
    rows = jnp.take(table, indices, axis=0)            # [B, L, D]
    if weights is not None:
        rows = rows * weights[..., None].astype(rows.dtype)
    out = rows.sum(axis=1)
    if mode == "mean":
        if weights is not None:
            denom = jnp.maximum(weights.sum(axis=1), 1e-9)[..., None]
        else:
            denom = jnp.asarray(indices.shape[1], dtype=rows.dtype)
        out = out / denom
    elif mode != "sum":
        raise ValueError(f"unknown mode {mode!r}")
    return out


def embedding_bag_flat_ref(table: jnp.ndarray, indices: jnp.ndarray,
                           weights: jnp.ndarray | None = None, *,
                           first_rows, pooling, mode: str = "sum"):
    """Bags of a bag size per table over one table array.

    table:      [sum(R), D]; table t starts at row first_rows[t]
    indices:    [B, sum(pooling)] int, table t's bag in its own columns,
                ids local to the table
    weights:    [B, sum(pooling)] float or None
    returns:    [B, T, D]
    """
    cols = np.cumsum([0, *pooling])
    first = np.repeat(np.asarray(first_rows, np.int32), pooling)
    rows = jnp.take(table, indices + first, axis=0)      # [B, sum(L), D]
    if weights is not None:
        rows = rows * weights[..., None].astype(rows.dtype)
    out = []
    for a, b in zip(cols[:-1], cols[1:]):
        bag = rows[:, a:b].sum(axis=1)
        if mode == "mean":
            denom = (jnp.maximum(weights[:, a:b].sum(axis=1), 1e-9)[:, None]
                     if weights is not None
                     else jnp.asarray(b - a, dtype=rows.dtype))
            bag = bag / denom
        elif mode != "sum":
            raise ValueError(f"unknown mode {mode!r}")
        out.append(bag)
    return jnp.stack(out, axis=1)


def embedding_bag_ragged_ref(table: jnp.ndarray, flat_indices: jnp.ndarray,
                             offsets: jnp.ndarray,
                             weights: jnp.ndarray | None = None,
                             mode: str = "sum") -> jnp.ndarray:
    """Ragged embedding bag (offsets form, like torch EmbeddingBag).

    flat_indices: [T] int, offsets: [B+1] int. Bag b covers
    flat_indices[offsets[b]:offsets[b+1]].
    """
    num_bags = offsets.shape[0] - 1
    rows = jnp.take(table, flat_indices, axis=0)       # [T, D]
    if weights is not None:
        rows = rows * weights[:, None].astype(rows.dtype)
    seg = jnp.searchsorted(offsets[1:], jnp.arange(flat_indices.shape[0]),
                           side="right")
    out = jops.segment_sum(rows, seg, num_segments=num_bags)
    if mode == "mean":
        counts = (offsets[1:] - offsets[:-1]).astype(out.dtype)
        out = out / jnp.maximum(counts, 1)[:, None]
    elif mode != "sum":
        raise ValueError(f"unknown mode {mode!r}")
    return out


def embedding_lookup_ref(table: jnp.ndarray, token_ids: jnp.ndarray) -> jnp.ndarray:
    """Plain gather (pooling=1 degenerate bag) — LM vocab embedding."""
    return jnp.take(table, token_ids, axis=0)
