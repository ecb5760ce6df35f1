"""Plain reference of the DLRM the configurations serve (Naumov et al.,
arXiv:1906.00091; the paper's §V sizes), and the weights made from the
seed.

Forward: bottom MLP over the dense features (ReLU after every layer),
a sum-pooled embedding bag per table, the dot interaction (every
pairwise dot product among the bottom output and the pooled rows, taken
in the upper-triangle order of `triu_indices`, after the bottom output
itself), and the top MLP (ReLU between layers, none after the last) to
one logit per query. The pair order and the parameter tree are the
served program's conventions; nothing else of the program is used.

The weights are drawn here from the seed, in one jitted call, in the
tree the program takes; the reference draws them again once the
program's state is gone.

`dtype` float32 is the reference: float32 rows and sums, matrix
products at `highest` precision. `dtype` bfloat16 is the control, the
same arithmetic one precision lower: rows, sums and activations in
bfloat16, matrix products at the default precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.generator import seed_key
from bench.work import table_pooling

CHUNK = 2048                  # queries per block of the reference


def _mlp_dims(cfg: dict) -> tuple:
    f = cfg["num_tables"] + 1
    top_in = cfg["bottom_mlp"][-1] + f * (f - 1) // 2
    return ((cfg["dense_features"], *cfg["bottom_mlp"]),
            (top_in, *cfg["top_mlp"]))


def mlp_shapes(cfg: dict) -> list[tuple[int, int]]:
    """(fan_in, fan_out) of every layer, bottom tower then top."""
    return [io for dims in _mlp_dims(cfg) for io in zip(dims[:-1], dims[1:])]


def step_flops(cfg: dict, batch: int) -> int:
    """Floating-point operations of one forward over `batch` queries: the
    bag additions, both MLP towers (multiply and add), and the full
    Gram matrix of the dot interaction."""
    t, d = cfg["num_tables"], cfg["dim"]
    bags = batch * sum(table_pooling(cfg)) * d
    mlps = sum(2 * batch * i * o for i, o in mlp_shapes(cfg))
    gram = 2 * batch * (t + 1) ** 2 * d
    return int(bags + mlps + gram)


def weight_count(cfg: dict) -> int:
    """Weights and biases of both MLP towers."""
    return sum(i * o + o for i, o in mlp_shapes(cfg))


def _tower(key, dims) -> dict:
    out = {}
    for i, (din, dout) in enumerate(zip(dims[:-1], dims[1:])):
        out[f"w{i}"] = (jax.random.normal(jax.random.fold_in(key, 2 * i),
                                          (din, dout), jnp.float32)
                        / np.sqrt(din))
        out[f"b{i}"] = 0.05 * jax.random.normal(
            jax.random.fold_in(key, 2 * i + 1), (dout,), jnp.float32)
    return out


@functools.partial(jax.jit, static_argnames=("dims", "num_tables", "rows",
                                             "dim"))
def _init(key, *, dims, num_tables: int, rows: int, dim: int):
    k_tab = jax.random.fold_in(key, 2)
    tables = jax.lax.map(
        lambda t: jax.random.normal(jax.random.fold_in(k_tab, t),
                                    (rows, dim), jnp.float32)
        / np.sqrt(dim), jnp.arange(num_tables))
    return {"bottom": _tower(jax.random.fold_in(key, 0), dims[0]),
            "embedding": {"tables": tables},
            "top": _tower(jax.random.fold_in(key, 1), dims[1])}


def init_params(seed: int, cfg: dict) -> dict:
    """The program's parameter tree, on the device, from the seed:
    tables [T, R, D] ~ N(0, 1/D), weights ~ N(0, 1/fan_in), biases
    ~ N(0, 0.05**2), all float32."""
    return _init(seed_key(seed, 1), dims=_mlp_dims(cfg),
                 num_tables=cfg["num_tables"], rows=cfg["rows"],
                 dim=cfg["dim"])


def _tower_apply(p: dict, x, dtype, prec, final_act: bool):
    n = len(p) // 2
    for i in range(n):
        x = (jnp.dot(x, p[f"w{i}"].astype(dtype), precision=prec)
             + p[f"b{i}"].astype(dtype))
        if i < n - 1 or final_act:
            x = jax.nn.relu(x)
    return x


@functools.partial(jax.jit, static_argnames=("dtype",))
def _block(params, idx, dense, *, dtype):
    """idx [CHUNK, T, L], dense [CHUNK, F] -> (logits [CHUNK], pooled
    [CHUNK, T, D]), both float32. One table at a time."""
    prec = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    tables = params["embedding"]["tables"]
    pooled = jax.lax.map(
        lambda ti: jnp.sum(jnp.take(ti[0], ti[1], axis=0).astype(dtype),
                           axis=1, dtype=dtype),
        (tables, jnp.swapaxes(idx, 0, 1)))             # [T, CHUNK, D]
    pooled = jnp.swapaxes(pooled, 0, 1)
    bottom = _tower_apply(params["bottom"], dense.astype(dtype), dtype,
                          prec, True)
    feats = jnp.concatenate([bottom[:, None, :], pooled], axis=1)
    gram = jnp.einsum("btd,bsd->bts", feats, feats, precision=prec)
    iu, ju = np.triu_indices(feats.shape[1], k=1)
    z = jnp.concatenate([bottom, gram[:, iu, ju]], axis=1)
    logit = _tower_apply(params["top"], z, dtype, prec, False)[:, 0]
    return logit.astype(jnp.float32), pooled.astype(jnp.float32)


def reference(seed: int, cfg: dict, indices: np.ndarray, dense: np.ndarray,
              select: np.ndarray, dtype=jnp.float32,
              want_pooled: bool = False):
    """Logits [N] (and pooled rows [N, T, D] when asked) of the queries
    `indices[select]` [N, T, L], `dense[select]` [N, F], CHUNK queries at
    a time."""
    params = init_params(seed, cfg)
    n = len(select)
    logits = np.empty(n, np.float32)
    pooled = (np.empty((n, cfg["num_tables"], cfg["dim"]), np.float32)
              if want_pooled else None)
    for s in range(0, n, CHUNK):
        m = min(CHUNK, n - s)
        idx = np.zeros((CHUNK,) + indices.shape[1:], np.int32)
        dn = np.zeros((CHUNK, dense.shape[1]), np.float32)
        idx[:m] = indices[select[s:s + m]]
        dn[:m] = dense[select[s:s + m]]
        lg, pl = _block(params, idx, dn, dtype=dtype)
        logits[s:s + m] = np.asarray(lg)[:m]
        if want_pooled:
            pooled[s:s + m] = np.asarray(pl)[:m]
    params["embedding"]["tables"].delete()
    return logits, pooled
