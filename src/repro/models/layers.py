"""DLRM's MLP towers and their init. Functional (dict params)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def dense_init(rng, shape, dtype, scale: float | None = None):
    """Truncated-normal fan-in init."""
    fan_in = shape[0] if len(shape) >= 2 else 1
    scale = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (jax.random.truncated_normal(rng, -2, 2, shape, jnp.float32)
            * scale).astype(dtype)


def mlp_tower_init(rng, dims: tuple[int, ...], dtype) -> dict:
    """Plain MLP tower (DLRM bottom/top)."""
    ks = jax.random.split(rng, len(dims) - 1)
    return {f"w{i}": dense_init(ks[i], (dims[i], dims[i + 1]), dtype)
            for i in range(len(dims) - 1)} | {
            f"b{i}": jnp.zeros((dims[i + 1],), dtype)
            for i in range(len(dims) - 1)}


def mlp_tower_apply(params: dict, x: jnp.ndarray, *, final_act: bool = False):
    n = len([k for k in params if k.startswith("w")])
    for i in range(n):
        x = x @ params[f"w{i}"] + params[f"b{i}"]
        if i < n - 1 or final_act:
            x = jax.nn.relu(x)
    return x

