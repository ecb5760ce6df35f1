"""Public jit'd wrappers around the embedding-bag kernel.

Backend selection:
  * 'pallas'    — the TPU kernel (interpret=True automatically on CPU hosts,
                  which executes the kernel body in Python for validation).
  * 'xla'       — the pure-jnp reference (production baseline; what stock
                  frameworks do — the paper's "off-the-shelf" analogue).
  * 'auto'      — pallas on TPU, xla elsewhere.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from . import ref
from .kernel import (EmbeddingBagOpts, embedding_bag_flat_pallas,
                     embedding_bag_pallas)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_batch(indices: jnp.ndarray, weights: jnp.ndarray | None, bb: int):
    """Pad the batch axis (-2 of [T, B, L]) up to a multiple of batch_block
    with zero-weight dummy bags."""
    batch = indices.shape[-2]
    pad = (-batch) % bb
    if pad == 0:
        return indices, weights, batch
    widths = [(0, 0)] * (indices.ndim - 2) + [(0, pad), (0, 0)]
    indices = jnp.pad(indices, widths)
    if weights is not None:
        weights = jnp.pad(weights, widths)
    return indices, weights, batch


@functools.partial(jax.jit, static_argnames=("mode", "opts"))
def embedding_bag_stacked(tables: jnp.ndarray, indices: jnp.ndarray,
                          weights: jnp.ndarray | None = None, *,
                          mode: str = "sum",
                          opts: EmbeddingBagOpts | None = None) -> jnp.ndarray:
    """The Pallas kernel over a table stack, one launch:
    [T,R,D] x [T,B,L] -> [T,B,D]."""
    opts = opts or EmbeddingBagOpts()
    opts = dataclasses.replace(opts, mode=mode,
                               interpret=opts.interpret or not _on_tpu())
    indices, weights, batch = _pad_batch(indices, weights, opts.batch_block)
    out = embedding_bag_pallas(tables, indices, weights, opts)
    return out[:, :batch]


@functools.partial(jax.jit, static_argnames=("first_rows", "pooling",
                                             "mode", "opts"))
def embedding_bag_flat(table: jnp.ndarray, indices: jnp.ndarray,
                       weights: jnp.ndarray | None = None, *,
                       first_rows: tuple, pooling: tuple, mode: str = "sum",
                       opts: EmbeddingBagOpts | None = None) -> jnp.ndarray:
    """The Pallas kernel over tables of their own rows and bag sizes, one
    launch: [sum(R),D] x [B,sum(L)] -> [B,T,D] (kernel.py)."""
    opts = opts or EmbeddingBagOpts()
    opts = dataclasses.replace(opts, mode=mode,
                               interpret=opts.interpret or not _on_tpu())
    indices, weights, batch = _pad_batch(indices, weights, opts.batch_block)
    out = embedding_bag_flat_pallas(table, indices, weights,
                                    first_rows=first_rows, pooling=pooling,
                                    opts=opts)
    return out[:batch]


@functools.partial(jax.jit, static_argnames=("mode", "backend", "opts"))
def embedding_bag(table: jnp.ndarray, indices: jnp.ndarray,
                  weights: jnp.ndarray | None = None, *, mode: str = "sum",
                  backend: str = "auto",
                  opts: EmbeddingBagOpts | None = None) -> jnp.ndarray:
    """Fixed-pooling embedding bag: [R,D] x [B,L] -> [B,D]."""
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "xla"
    if backend == "xla":
        return ref.embedding_bag_ref(table, indices, weights, mode=mode)
    if backend != "pallas":
        raise ValueError(f"unknown backend {backend!r}")
    out = embedding_bag_stacked(
        table[None], indices[None],
        None if weights is None else weights[None], mode=mode, opts=opts)
    return out[0]


def embedding_lookup(table: jnp.ndarray, token_ids: jnp.ndarray, *,
                     backend: str = "auto",
                     opts: EmbeddingBagOpts | None = None) -> jnp.ndarray:
    """Plain gather (LM vocab embedding) as a pooling=1 bag.

    token_ids: any int shape [...]; returns [..., D].
    """
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "xla"
    if backend == "xla":
        return ref.embedding_lookup_ref(table, token_ids)
    flat = token_ids.reshape(-1, 1)
    out = embedding_bag(table, flat, mode="sum", backend=backend, opts=opts)
    return out.reshape(*token_ids.shape, table.shape[1])
