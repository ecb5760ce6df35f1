"""Optimizers tuned for the workloads here.

* adamw            — f32 moments + f32 master copy (highest fidelity)
* sgdm             — momentum SGD
* rowwise_adagrad  — per-row accumulator for embedding tables (DLRM standard;
                     one f32 scalar per row instead of per element)

All are functional: init(params) -> state; update(params, grads, state) ->
(params, state).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


# -- AdamW ------------------------------------------------------------------

def adamw_init(params: Any) -> dict:
    return {
        "m": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
        "v": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
        "master": jax.tree.map(lambda p: p.astype(jnp.float32), params),
        "count": jnp.zeros((), jnp.int32),
    }


def adamw_update(params, grads, state, *, lr=1e-4, b1=0.9, b2=0.999,
                 eps=1e-8, wd=0.01):
    c = state["count"] + 1
    def upd(m, v, master, g):
        g = g.astype(jnp.float32)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        mh = m / (1 - b1 ** c.astype(jnp.float32))
        vh = v / (1 - b2 ** c.astype(jnp.float32))
        master = master - lr * (mh / (jnp.sqrt(vh) + eps) + wd * master)
        return m, v, master
    out = jax.tree.map(upd, state["m"], state["v"], state["master"], grads)
    m = jax.tree.map(lambda t: t[0], out, is_leaf=lambda x: isinstance(x, tuple))
    v = jax.tree.map(lambda t: t[1], out, is_leaf=lambda x: isinstance(x, tuple))
    master = jax.tree.map(lambda t: t[2], out,
                          is_leaf=lambda x: isinstance(x, tuple))
    params = jax.tree.map(lambda p, w: w.astype(p.dtype), params, master)
    return params, {"m": m, "v": v, "master": master, "count": c}


# -- SGD momentum --------------------------------------------------------------

def sgdm_init(params):
    return {"mom": jax.tree.map(lambda p: jnp.zeros_like(p), params)}


def sgdm_update(params, grads, state, *, lr=1e-2, beta=0.9):
    mom = jax.tree.map(lambda m, g: beta * m + g.astype(m.dtype),
                       state["mom"], grads)
    params = jax.tree.map(lambda p, m: p - lr * m.astype(p.dtype), params, mom)
    return params, {"mom": mom}


# -- Row-wise Adagrad (embedding tables) ---------------------------------------

def rowwise_adagrad_init(tables):
    """tables: [..., R, D] -> one accumulator scalar per row."""
    return {"acc": jax.tree.map(
        lambda t: jnp.zeros(t.shape[:-1], jnp.float32), tables)}


def rowwise_adagrad_update(tables, grads, state, *, lr=0.01, eps=1e-8):
    def upd(t, g, a):
        g32 = g.astype(jnp.float32)
        a = a + jnp.mean(jnp.square(g32), axis=-1)
        scale = lr / (jnp.sqrt(a) + eps)
        return (t.astype(jnp.float32) - scale[..., None] * g32).astype(t.dtype), a
    out = jax.tree.map(upd, tables, grads, state["acc"])
    new_t = jax.tree.map(lambda x: x[0], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    new_a = jax.tree.map(lambda x: x[1], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    return new_t, {"acc": new_a}


# -- Gradient compression (distributed-optimization trick) --------------------

def compress_grads(grads, dtype=jnp.bfloat16):
    """Cast gradients before the DP all-reduce; returns (compressed, residual
    correction closure state) for error feedback."""
    comp = jax.tree.map(lambda g: g.astype(dtype), grads)
    resid = jax.tree.map(lambda g, c: g.astype(jnp.float32)
                         - c.astype(jnp.float32), grads, comp)
    return comp, resid


def apply_error_feedback(grads, resid):
    if resid is None:
        return grads
    return jax.tree.map(lambda g, r: g.astype(jnp.float32) + r, grads, resid)
