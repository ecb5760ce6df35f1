"""Traffic made from the seed: per-table Zipf row ids, dense features and
arrival times, as a traffic file under `bench/traffic/` describes them.

The access model is the paper's (§III-B, Table III), as
`repro/core/access_patterns.py` builds it: ranks drawn by inverse-CDF
sampling from a Zipf law over the table's rows, scattered to physical
rows by a random permutation per table, so hot rows are not contiguous.
The exponents below are that module's `calibrate_alpha` of each Table III
unique-access target at the paper's reference batch (500,000 rows,
2048 x 150 accesses), stored so that no run bisects them again.

The draw runs on the device in 32-bit fixed point: `u` is 32 random
bits, the CDF is scaled to 2**32, and the first rank whose CDF reaches
`u` is found from a bucket table over the top bits of `u` followed by a
few comparisons — the same answer as a binary search over the CDF, at
the cost of a handful of gathers per draw instead of twenty.

Geometry per table. A configuration's `rows` and `pooling` are each one
int for every table or a list of `num_tables` ints; a traffic file's
`hotness` is one name of `HOTNESS_ALPHA` or a list of `num_tables`
names. Each table is drawn from its own rows, exponent and bag size.
The indices of N queries are laid out as follows:

- `pooling` an int L: `[N, T, L]` int32, table t's bag in `[:, t]`.
- `pooling` a list: `[N, sum(pooling)]` int32, table t's bag in columns
  `off[t]:off[t+1]`, `off = cumsum([0] + pooling)`; no bag is padded.

Where every table has the same rows, bag size and hotness, one jitted
draw covers the stack (`_draw_batch`), and the ids are the same for a
seed whichever way the geometry is spelled; otherwise each table is
drawn on its own key (`_draw_tables`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import work

#: Zipf exponent per Table III hotness level (0.0: uniform).
HOTNESS_ALPHA = {
    "high_hot": 1.2790602988294633,     # 4.05 % unique rows per batch
    "med_hot": 0.9272848391426343,      # 20.5 %
    "low_hot": 0.24599367581824477,     # 44.99 % (46.21 clamped under uniform)
    "random": 0.0,                      # 45.90 % (the uniform bound)
}

#: most comparisons a draw may need after the bucket lookup
MAX_SPAN = 8


def seed_key(seed: int, stream: int) -> jax.Array:
    """A key per (seed, stream). Any integer seed: its low and high 32
    bits are folded in separately, so seeds past 2**31 stay distinct."""
    s = int(seed) % (1 << 64)
    key = jax.random.key(0)
    key = jax.random.fold_in(key, s & 0xFFFFFFFF)
    key = jax.random.fold_in(key, s >> 32)
    return jax.random.fold_in(key, stream)


def zipf_tables(alpha: float, rows: int) -> tuple:
    """(cdf [rows] uint32, bucket [2**bits + 1] int32, shift, span)."""
    ranks = np.arange(1, rows + 1, dtype=np.float64)
    w = ranks ** (-alpha) if alpha > 0 else np.ones_like(ranks)
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    cdf_u = np.minimum(np.floor(cdf * 2.0 ** 32), 2 ** 32 - 1).astype(
        np.uint64)
    for bits in (20, 22, 24):
        thresholds = np.arange(2 ** bits + 1, dtype=np.uint64) << np.uint64(
            32 - bits)
        bucket = np.minimum(np.searchsorted(cdf_u, thresholds, side="left"),
                            rows - 1)
        span = int(np.max(np.diff(bucket)))
        if span <= MAX_SPAN:
            return (cdf_u.astype(np.uint32), bucket.astype(np.int32),
                    32 - bits, span)
    raise ValueError(f"alpha {alpha}: a draw needs more than {MAX_SPAN} "
                     f"comparisons even with 2**24 buckets")


@functools.partial(jax.jit, static_argnames=("num_tables", "rows"))
def _permutations(key, *, num_tables: int, rows: int) -> jax.Array:
    keys = jax.random.split(key, num_tables)
    return jax.vmap(
        lambda k: jax.random.permutation(k, rows).astype(jnp.int32))(keys)


def _rank(u, cdf, bucket, shift: int, span: int):
    """The first rank whose fixed-point CDF reaches each draw `u`."""
    lo = bucket[(u >> shift).astype(jnp.int32)]
    rank = lo
    for j in range(span):
        rank = rank + (cdf[jnp.minimum(lo + j, cdf.shape[0] - 1)]
                       < u).astype(jnp.int32)
    return rank


@functools.partial(jax.jit, static_argnames=(
    "batch", "pooling", "dense_features", "shift", "span"))
def _draw_batch(key, cdf, bucket, perms, *, batch: int, pooling: int,
                dense_features: int, shift: int, span: int):
    num_tables, rows = perms.shape
    k_rows, k_dense = jax.random.split(key)
    u = jax.random.bits(k_rows, (batch, num_tables, pooling), jnp.uint32)
    rank = _rank(u, cdf, bucket, shift, span)
    table = jnp.arange(num_tables, dtype=jnp.int32)[None, :, None]
    ids = perms.reshape(-1)[table * rows + rank]
    dense = jax.random.normal(k_dense, (batch, dense_features), jnp.float32)
    return ids, dense


@functools.partial(jax.jit, static_argnames=("rows",))
def _table_permutations(key, *, rows: tuple) -> tuple:
    return tuple(jax.random.permutation(jax.random.fold_in(key, t),
                                        r).astype(jnp.int32)
                 for t, r in enumerate(rows))


@functools.partial(jax.jit, static_argnames=(
    "zipf_of", "pooling", "shifts", "spans", "batch", "dense_features"))
def _draw_tables(key, cdfs, buckets, perms, *, zipf_of: tuple,
                 pooling: tuple, shifts: tuple, spans: tuple, batch: int,
                 dense_features: int):
    """Table t's bags from its own key, Zipf table `zipf_of[t]` and
    permutation, joined into the flat [batch, sum(pooling)] layout."""
    k_rows, k_dense = jax.random.split(key)
    out = []
    for t, (z, size, perm) in enumerate(zip(zipf_of, pooling, perms)):
        u = jax.random.bits(jax.random.fold_in(k_rows, t), (batch, size),
                            jnp.uint32)
        out.append(perm[_rank(u, cdfs[z], buckets[z], shifts[z], spans[z])])
    dense = jax.random.normal(k_dense, (batch, dense_features), jnp.float32)
    return jnp.concatenate(out, axis=1), dense


class Traffic:
    """The queries of one run: `indices` int32 in the layout above and
    `dense` [N, F] float32, on the host."""

    def __init__(self, indices, dense):
        self.indices = indices
        self.dense = dense

    def __len__(self) -> int:
        return len(self.indices)


def _drawer(cfg: dict, traffic: dict, key, batch: int):
    """The jitted draw of one batch from its key: (ids, dense)."""
    tables, features = cfg["num_tables"], cfg["dense_features"]
    rows, pooling = work.table_rows(cfg), work.table_pooling(cfg)
    alphas = [HOTNESS_ALPHA[h] for h in
              work.per_table(traffic["hotness"], tables, "hotness")]
    if len(set(rows)) == len(set(pooling)) == len(set(alphas)) == 1:
        cdf, bucket, shift, span = zipf_tables(alphas[0], rows[0])
        perms = _permutations(jax.random.fold_in(key, 0),
                              num_tables=tables, rows=rows[0])
        return functools.partial(
            _draw_batch, cdf=jnp.asarray(cdf), bucket=jnp.asarray(bucket),
            perms=perms, batch=batch, pooling=pooling[0],
            dense_features=features, shift=shift, span=span)
    zipf: dict = {}                  # (alpha, rows) -> its tables' index
    for pair in zip(alphas, rows):
        zipf.setdefault(pair, len(zipf))
    made = [zipf_tables(a, r) for a, r in zipf]
    perms = _table_permutations(jax.random.fold_in(key, 0), rows=tuple(rows))
    return functools.partial(
        _draw_tables, cdfs=tuple(jnp.asarray(m[0]) for m in made),
        buckets=tuple(jnp.asarray(m[1]) for m in made), perms=perms,
        zipf_of=tuple(zipf[p] for p in zip(alphas, rows)),
        pooling=tuple(pooling), shifts=tuple(m[2] for m in made),
        spans=tuple(m[3] for m in made), batch=batch,
        dense_features=features)


def make_traffic(cfg: dict, traffic: dict, seed: int, batches: int,
                 batch: int) -> Traffic:
    """Draw `batches` full batches of queries on the device and bring
    them to the host. Batch i is the same for a seed whatever `batches`
    is, so a longer pool only adds batches."""
    key = seed_key(seed, 2)
    draw = _drawer(cfg, traffic, key, batch)
    width = sum(work.table_pooling(cfg))
    indices = np.empty((batches * batch, width), np.int32)
    dense = np.empty((batches * batch, cfg["dense_features"]), np.float32)
    pending = draw(jax.random.fold_in(key, 1))
    for i in range(batches):
        nxt = (draw(jax.random.fold_in(key, i + 2))
               if i + 1 < batches else None)
        ids, dn = jax.device_get(pending)
        indices[i * batch:(i + 1) * batch] = ids.reshape(batch, width)
        dense[i * batch:(i + 1) * batch] = dn
        pending = nxt
    if np.ndim(cfg["pooling"]) == 0:
        indices = indices.reshape(len(indices), cfg["num_tables"],
                                  cfg["pooling"])
    return Traffic(indices, dense)


def open_loop_due(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Poisson arrivals at `rate_qps` with the same count for every seed:
    N = rate x seconds exponential gaps, scaled so that the (N+1)-th
    arrival would fall exactly at the window's end."""
    n = int(round(traffic["rate_qps"] * seconds))
    rng = np.random.default_rng([int(seed) % (1 << 64), 7])
    t = np.cumsum(rng.exponential(1.0, n + 1))
    return (t[:-1] / t[-1] * seconds).astype(np.float64)
