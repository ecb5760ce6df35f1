"""Plain reference of MLPerf's DLRM-DCNv2 (mlcommons/training
`recommendation_v2/torchrec_dlrm`, mlcommons/inference
`recommendation/dlrm_v2`; TorchRec's `DLRM_DCN` with its
`LowRankCrossNet`, after DCN V2, Wang et al., arXiv:2008.13535), and the
weights made from the seed.

Forward, as `DLRM_DCN` computes it: the bottom MLP over the dense
features (ReLU after every layer); a sum-pooled bag per table, table t's
ids in its own columns of the flat `[N, sum(pooling)]` indices; x_0, the
bottom output and the 26 pooled rows joined dense first and flattened to
(T+1)·D features; `dcn_layers` cross layers,
x_{l+1} = x_0 ⊙ (U_l (V_l x_l) + b_l) + x_l, V_l mapping (T+1)·D to
`dcn_rank` with no bias and U_l mapping back with bias b_l; the top MLP
(ReLU between layers, none after the last) to one logit per query.

Departures from TorchRec and the MLPerf reference, each a choice of the
benchmark and none of the arithmetic:

- The weights are drawn here from the seed, not trained: weights
  ~ N(0, 1/fan_in) (TorchRec: Xavier-normal for V and U, PyTorch's
  uniform for the MLPs), biases ~ N(0, 0.05**2) (TorchRec: the cross
  biases start at zero), table rows ~ N(0, 1/D).
- The answer is the logit; MLPerf's inference reference applies a
  sigmoid after it.
- Tables split row-wise over chips hold a slice of the published rows;
  the configuration's `rows` is what one chip holds, and the pooled row
  of such a table is that slice's partial sum, here as in the program.
- The parameter tree is the served program's (`{"bottom", "embedding":
  {"tables": [sum(rows), D]}, "cross": {"v<l>", "u<l>", "b<l>"}, "top"}`
  with V stored as `[F, rank]` and U as `[rank, F]`, so that x @ V is
  TorchRec's `linear(x, V_kernels[l])`); nothing else of the program is
  used.

The tables are drawn in chunks of at most `DRAW_ROWS` rows, each written
in place into the one flat array by a loop, so that the device holds the
array and one chunk, not two copies of the array.

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
from bench.work import table_pooling, table_rows

CHUNK = 2048                  # queries per block of the reference
DRAW_ROWS = 1 << 19           # most table rows drawn at once


def _features(cfg: dict) -> int:
    return (cfg["num_tables"] + 1) * cfg["dim"]


def _mlp_dims(cfg: dict) -> tuple:
    return ((cfg["dense_features"], *cfg["bottom_mlp"]),
            (_features(cfg), *cfg["top_mlp"]))


def mlp_shapes(cfg: dict) -> list[tuple[int, int]]:
    """(fan_in, fan_out) of every MLP layer, bottom tower then top."""
    return [io for dims in _mlp_dims(cfg) for io in zip(dims[:-1], dims[1:])]


def _cross(cfg: dict) -> tuple:
    args = cfg["model_args"]
    return args["dcn_layers"], args["dcn_rank"]


def step_flops(cfg: dict, batch: int) -> int:
    """Floating-point operations of one forward over `batch` queries: the
    bag additions, both MLP towers (multiply and add), and per cross layer
    the two low-rank products (multiply and add) and three elementwise
    operations per feature (bias, the product with x_0, the residual)."""
    f = _features(cfg)
    layers, rank = _cross(cfg)
    bags = batch * sum(table_pooling(cfg)) * cfg["dim"]
    mlps = sum(2 * batch * i * o for i, o in mlp_shapes(cfg))
    cross = layers * (2 * 2 * batch * f * rank + 3 * batch * f)
    return int(bags + mlps + cross)


def weight_count(cfg: dict) -> int:
    """Weights and biases of both MLP towers, and each cross layer's V, U
    and b."""
    f = _features(cfg)
    layers, rank = _cross(cfg)
    return (sum(i * o + o for i, o in mlp_shapes(cfg))
            + layers * (2 * f * rank + f))


def _normal(key, shape, fan_in: int):
    return jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)


def _tower(key, dims) -> dict:
    out = {}
    for i, (din, dout) in enumerate(zip(dims[:-1], dims[1:])):
        out[f"w{i}"] = _normal(jax.random.fold_in(key, 2 * i), (din, dout),
                               din)
        out[f"b{i}"] = 0.05 * jax.random.normal(
            jax.random.fold_in(key, 2 * i + 1), (dout,), jnp.float32)
    return out


@functools.partial(jax.jit, static_argnames=("dims", "rows", "dim",
                                             "layers", "rank"))
def _init(key, *, dims, rows: tuple, dim: int, layers: int, rank: int):
    k_tab = jax.random.fold_in(key, 2)
    tables = jnp.zeros((sum(rows), dim), jnp.float32)
    first = 0
    for t, r in enumerate(rows):
        k_t = jax.random.fold_in(k_tab, t)
        if r <= DRAW_ROWS:
            tables = jax.lax.dynamic_update_slice(
                tables, _normal(jax.random.fold_in(k_t, 0), (r, dim), dim),
                (first, 0))
        else:
            # chunks of DRAW_ROWS rows, the last one ending at the table's
            # end (over rows of the one before it); a loop, so that one
            # chunk is drawn at a time
            def chunk(c, tables, k_t=k_t, r=r, first=first):
                lo = jnp.minimum(c * DRAW_ROWS, r - DRAW_ROWS)
                return jax.lax.dynamic_update_slice(
                    tables, _normal(jax.random.fold_in(k_t, c),
                                    (DRAW_ROWS, dim), dim), (first + lo, 0))
            tables = jax.lax.fori_loop(0, -(-r // DRAW_ROWS), chunk, tables)
        first += r
    f = dims[1][0]
    k_cross = jax.random.fold_in(key, 3)
    cross = {}
    for l in range(layers):
        k = jax.random.fold_in(k_cross, l)
        cross[f"v{l}"] = _normal(jax.random.fold_in(k, 0), (f, rank), f)
        cross[f"u{l}"] = _normal(jax.random.fold_in(k, 1), (rank, f), rank)
        cross[f"b{l}"] = 0.05 * jax.random.normal(jax.random.fold_in(k, 2),
                                                  (f,), jnp.float32)
    return {"bottom": _tower(jax.random.fold_in(key, 0), dims[0]),
            "embedding": {"tables": tables},
            "cross": cross,
            "top": _tower(jax.random.fold_in(key, 1), dims[1])}


def init_params(seed: int, cfg: dict) -> dict:
    """The program's parameter tree, on the device, from the seed, all
    float32: the `[sum(rows), D]` table drawn table by table in chunks."""
    layers, rank = _cross(cfg)
    return _init(seed_key(seed, 1), dims=_mlp_dims(cfg),
                 rows=tuple(int(r) for r in table_rows(cfg)),
                 dim=cfg["dim"], layers=layers, rank=rank)


def _tower_apply(p: dict, x, dtype, prec, final_act: bool):
    n = len(p) // 2
    for i in range(n):
        x = (jnp.dot(x, p[f"w{i}"].astype(dtype), precision=prec)
             + p[f"b{i}"].astype(dtype))
        if i < n - 1 or final_act:
            x = jax.nn.relu(x)
    return x


@functools.partial(jax.jit, static_argnames=("rows", "pooling", "dtype"))
def _block(params, idx, dense, *, rows: tuple, pooling: tuple, dtype):
    """idx [CHUNK, sum(pooling)], dense [CHUNK, F] -> (logits [CHUNK],
    pooled [CHUNK, T, D]), both float32. One table at a time."""
    prec = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    tables = params["embedding"]["tables"]
    first, col, pooled = 0, 0, []
    for r, n in zip(rows, pooling):
        ids = idx[:, col:col + n] + first
        pooled.append(jnp.sum(jnp.take(tables, ids, axis=0).astype(dtype),
                              axis=1, dtype=dtype))
        first += r
        col += n
    bottom = _tower_apply(params["bottom"], dense.astype(dtype), dtype,
                          prec, True)
    x0 = jnp.concatenate([bottom, *pooled], axis=1)
    x = x0
    cross = params["cross"]
    for l in range(len(cross) // 3):
        v = jnp.dot(x, cross[f"v{l}"].astype(dtype), precision=prec)
        x = x0 * (jnp.dot(v, cross[f"u{l}"].astype(dtype), precision=prec)
                  + cross[f"b{l}"].astype(dtype)) + x
    logit = _tower_apply(params["top"], x, dtype, prec, False)[:, 0]
    return logit.astype(jnp.float32), jnp.stack(pooled, 1).astype(
        jnp.float32)


def reference(seed: int, cfg: dict, indices: np.ndarray, dense: np.ndarray,
              select: np.ndarray, dtype=jnp.float32,
              want_pooled: bool = False):
    """Logits [N] (and pooled rows [N, T, D] when asked) of the queries
    `indices[select]` [N, sum(pooling)], `dense[select]` [N, F], CHUNK
    distinct queries at a time: each distinct entry of `select` is
    computed once."""
    params = init_params(seed, cfg)
    rows = tuple(int(r) for r in table_rows(cfg))
    pooling = tuple(int(n) for n in table_pooling(cfg))
    uniq, inv = np.unique(np.asarray(select), return_inverse=True)
    n = len(uniq)
    logits = np.empty(n, np.float32)
    pooled = (np.empty((n, cfg["num_tables"], cfg["dim"]), np.float32)
              if want_pooled else None)
    for s in range(0, n, CHUNK):
        m = min(CHUNK, n - s)
        idx = np.zeros((CHUNK, sum(pooling)), np.int32)
        dn = np.zeros((CHUNK, dense.shape[1]), np.float32)
        idx[:m] = indices[uniq[s:s + m]].reshape(m, -1)
        dn[:m] = dense[uniq[s:s + m]]
        lg, pl = _block(params, idx, dn, rows=rows, pooling=pooling,
                        dtype=dtype)
        logits[s:s + m] = np.asarray(lg)[:m]
        if want_pooled:
            pooled[s:s + m] = np.asarray(pl)[:m]
    params["embedding"]["tables"].delete()
    return logits[inv], None if pooled is None else pooled[inv]
