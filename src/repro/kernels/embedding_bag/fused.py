"""Fused warm-cache lookup kernel: hit-gather + pooled reduce in ONE Pallas
launch, plus the miss-list the host cold path consumes (paper §IV-B/§IV-C
pushed into the kernel).

The tiered parameter server used to resolve every index in Python tier
logic: probe the warm tag store, read hit payloads back to the host, gather
misses, scatter everything into a dense [B, L, D] block, then hand that to
the pooling reduction. This module replaces the warm-hit half of that round
trip with a single kernel launch over the device-resident cache payload
(`DeviceWarmCache.data`):

  inputs   cache [C, D]   — warm payload, device-resident
           slots [B, L]   — host-built slot-map per (bag, position):
                              -1                    miss (zero contribution,
                                                    emitted on the miss-list)
                              < -1                  padding (zero contribution,
                                                    NOT on the miss-list —
                                                    the wrapper's dummy bags)
                              [0, num_hot)          hot-block row (when `hot`
                                                    is passed)
                              [num_hot, num_hot+C)  cache slot + num_hot
           rows  [B, L]   — raw row ids (only read for the miss-list)
           weights [B, L] — optional per-lookup scales
           hot [K, D]     — optional VMEM-pinned hot block (L2-pin analogue)
  outputs  pooled [B, D]  — per-bag sum/mean with ZERO contribution at miss
                            and pad positions (the kernel's one output)
           miss_rows      — distinct missing raw row ids (sorted)
           miss_pos       — flat b*L+i occurrence positions (ascending)

The miss-list is read off the host-built slot-map (`_miss_list_from_slots`,
one `np.unique`) for every backend: the host already knows each MISS
position when it builds the map, so the kernel does not emit it. An
in-kernel list would need `B·L` words of SMEM (1.2 MB at the serving
batch B=2048, L=150 — more than v5e's 1 MiB SMEM) and a per-miss scan of
the distinct rows seen so far, quadratic in a cold batch's misses.

Bit-exactness contract (float32, the serving dtype): `pooled` equals
`ref.embedding_bag_ref` evaluated on a table whose missing rows are zeroed
— at 100% residency that is the dense reference itself. Four empirically
pinned-down rules make this hold on the CPU (see tests/test_kernel_fused.py):

  * the reduction must be a vector reduce over a gathered [L, D] bag
    buffer (`jnp.sum(axis=0)`), never a sequential scalar accumulation —
    XLA's reduce orders differently and drifts by 1 ULP;
  * weights scale each assembled row in place before the bag reduce
    reads it — a multiply fused into the reduce contracts to an FMA and
    drifts by 1 ULP;
  * mean-mode division happens only after the full numerator is assembled,
    and miss-containing bags are later RECOMPUTED whole (position order)
    by `complete_miss_bags`, never "completed" by adding cold rows to the
    partial sum out of order;
  * the mean normalization runs as an eager epilogue OUTSIDE the launch:
    a divide-by-L inside the traced kernel is a divide by a compile-time
    constant, which XLA strength-reduces to a reciprocal multiply — 1 ULP
    off the reference's eager division by a runtime scalar operand.

The kernel therefore assembles each grid step's bags into one flat
[batch_block * L, D] VMEM buffer (cache rows via `pltpu.make_async_copy`
row DMAs `prefetch_distance` deep, hot rows from VMEM, zeros at
miss/pad positions) and reduces each bag with a single VPU `sum(axis=0)`.
Grid steps share no state, so the batch axis is `parallel`.

Backends mirror ops.py: 'pallas' (interpret=True automatically on CPU) for
the TPU launch, 'xla' — an *eager* pure-jnp composition of exactly the
reference ops (bit-exact by construction, and fast on CPU hosts where
interpret-mode Pallas would crawl), 'auto' picks per platform. Layout note:
the TPU path prefers D a multiple of 128 (lane dim); interpret mode and the
xla variant take any D.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref

MISS = -1          # slot-map sentinel: miss — zero contribution + emission
PAD = -2           # slot-map sentinel: padded dummy bag — zero, no emission


@dataclasses.dataclass(frozen=True)
class FusedLookupOpts:
    """Tuning knobs (same mechanism analogues as EmbeddingBagOpts)."""

    prefetch_distance: int = 8   # cache-row DMAs in flight
    batch_block: int = 8         # bags per grid step
    interpret: bool = False      # CPU validation mode

    def vmem_bytes(self, pooling: int, dim: int, itemsize: int = 4) -> int:
        bag_buf = self.batch_block * max(1, pooling) * dim * itemsize
        out = self.batch_block * dim * itemsize
        return bag_buf + out


@dataclasses.dataclass(frozen=True)
class FusedLookupResult:
    """pooled stays on device; the miss-list is host-side numpy (its
    consumer is the host cold path)."""

    pooled: jnp.ndarray      # [B, D] table dtype
    miss_rows: np.ndarray    # [n_distinct] int32, sorted ascending
    miss_pos: np.ndarray     # [n_occurrences] int32 flat b*L+i, ascending

    @property
    def fully_resident(self) -> bool:
        return self.miss_rows.size == 0


def _fused_kernel(slot_ref, w_ref, cache_ref, hot_ref, out_ref,
                  buf_ref, sem_ref, *, pooling: int, distance: int,
                  num_hot: int, has_weights: bool):
    """One grid step: `batch_block` bags through the flat assembly buffer.

    slot_ref: SMEM [bb, L] int32 slot-map (scalar core: DMA addressing)
    w_ref:    SMEM [bb, L] f32 or None (one scalar per position)
    cache_ref: HBM [C, D] warm payload (memory_space=ANY; manual DMA only)
    hot_ref:  VMEM [K, D] or None
    out_ref:  VMEM [bb, D]
    buf_ref:  VMEM scratch [bb * L, D] — the per-step assembly buffer
    sem_ref:  DMA semaphores [distance]
    """
    bb = out_ref.shape[0]
    total = bb * pooling
    f32 = jnp.float32

    def start_fetch(t):
        """Begin the cache-row DMA for flat step t (warm slots only)."""
        slot = slot_ref[t // pooling, t % pooling]

        @pl.when(slot >= num_hot)
        def _():
            pltpu.make_async_copy(
                cache_ref.at[slot - num_hot], buf_ref.at[t],
                sem_ref.at[jax.lax.rem(t, distance)]
            ).start()

    # Prologue: fill the pipeline `distance` deep.
    for j in range(min(distance, total)):
        start_fetch(j)

    def body(t, _):
        s = t // pooling
        i = t % pooling
        slot = slot_ref[s, i]

        # Assemble position t of the flat buffer from its tier.
        @pl.when(slot >= num_hot)
        def _():
            pltpu.make_async_copy(
                cache_ref.at[slot - num_hot], buf_ref.at[t],
                sem_ref.at[jax.lax.rem(t, distance)]
            ).wait()

        if num_hot > 0:
            @pl.when(jnp.logical_and(slot >= 0, slot < num_hot))
            def _():
                safe = jnp.minimum(slot, num_hot - 1)
                buf_ref[pl.ds(t, 1), :] = hot_ref[pl.ds(safe, 1), :]

        @pl.when(slot < 0)
        def _():
            buf_ref[pl.ds(t, 1), :] = jnp.zeros((1, buf_ref.shape[1]),
                                                buf_ref.dtype)

        if has_weights:
            # scale in place, one position per step: the product is
            # rounded to the buffer dtype before the bag reduce reads it,
            # as in the reference's separate multiply (a multiply fused
            # into the reduce would contract to an FMA and drift 1 ULP)
            buf_ref[pl.ds(t, 1), :] = (buf_ref[pl.ds(t, 1), :]
                                       * w_ref[s, i].astype(buf_ref.dtype))

        # Keep the pipeline full.
        @pl.when(t + distance < total)
        def _():
            start_fetch(t + distance)

        # Bag boundary: ONE vector reduce over the assembled [L, D] bag —
        # the shape XLA's reference reduction uses, hence bit-exact. The
        # kernel always emits the raw (weighted) SUM; mean normalization
        # is the wrapper's eager epilogue (see module docstring).
        @pl.when(i == pooling - 1)
        def _():
            bag = buf_ref[pl.ds(s * pooling, pooling), :].astype(f32)  # [L, D]
            val = jnp.sum(bag, axis=0)
            out_ref[pl.ds(s, 1), :] = val[None, :].astype(out_ref.dtype)

        return 0

    jax.lax.fori_loop(0, total, body, 0)


def fused_warm_lookup_pallas(cache: jnp.ndarray, slots: jnp.ndarray,
                             weights: jnp.ndarray | None = None,
                             hot: jnp.ndarray | None = None, *,
                             opts: FusedLookupOpts = FusedLookupOpts()):
    """Raw kernel launch. B % batch_block == 0 (wrapper pads).

    Always emits the raw (weighted) per-bag SUM [B, D] — mean
    normalization is the wrapper's eager epilogue.
    """
    batch, pooling = slots.shape
    cache_rows, dim = cache.shape
    bb = opts.batch_block
    if batch % bb:
        raise ValueError(f"batch {batch} not divisible by batch_block {bb}")
    num_hot = int(hot.shape[0]) if hot is not None else 0
    has_weights = weights is not None
    distance = max(1, min(opts.prefetch_distance, bb * pooling))

    kernel = functools.partial(
        _fused_kernel, pooling=pooling, distance=distance, num_hot=num_hot,
        has_weights=has_weights)

    in_specs = [
        pl.BlockSpec((bb, pooling), lambda b: (b, 0), memory_space=pltpu.SMEM),
        (pl.BlockSpec((bb, pooling), lambda b: (b, 0),
                      memory_space=pltpu.SMEM) if has_weights else None),
        pl.BlockSpec(memory_space=pl.ANY),     # cache payload stays in HBM
        (pl.BlockSpec((num_hot, dim), lambda b: (0, 0)) if num_hot else None),
    ]
    inputs = [slots.astype(jnp.int32),
              weights.astype(jnp.float32) if has_weights else None,
              cache,
              hot if num_hot else None]
    live = [i for i, s in enumerate(in_specs) if s is not None]

    def kernel_wrapper(*refs):
        args = [None] * 4
        for j, i in enumerate(live):
            args[i] = refs[j]
        kernel(*args, *refs[len(live):])

    return pl.pallas_call(
        kernel_wrapper,
        grid=(batch // bb,),
        in_specs=[in_specs[i] for i in live],
        out_specs=pl.BlockSpec((bb, dim), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, dim), cache.dtype),
        scratch_shapes=[
            pltpu.VMEM((bb * pooling, dim), cache.dtype),  # DMA dst dtype
            pltpu.SemaphoreType.DMA((distance,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=opts.interpret,
        name="fused_embedding_bag",
    )(*[inputs[i] for i in live])


def fused_warm_lookup_xla(cache: jnp.ndarray, slots: jnp.ndarray,
                          rows: jnp.ndarray,
                          weights: jnp.ndarray | None = None,
                          hot: jnp.ndarray | None = None, *,
                          mode: str = "sum") -> jnp.ndarray:
    """Eager pure-jnp fused dataflow (the CPU-host production path).

    Composes exactly the reference ops — gather, elementwise select,
    multiply, `sum(axis=1)`, late divide — EAGERLY (a jitted wrapper would
    re-fuse mul+sum and drift 1 ULP), so the pooled output is bit-exact
    with `embedding_bag_ref` on the miss-zeroed table by construction.
    Returns only the pooled block (`fused_warm_lookup` reads the miss-list
    off the slot-map).
    """
    cache_rows = cache.shape[0]
    num_hot = int(hot.shape[0]) if hot is not None else 0
    slots = jnp.asarray(slots)
    warm_slot = jnp.clip(slots - num_hot, 0, max(cache_rows - 1, 0))
    gathered = jnp.where((slots >= num_hot)[..., None],
                         jnp.take(cache, warm_slot, axis=0),
                         jnp.zeros((), cache.dtype))          # [B, L, D]
    if num_hot:
        hot_slot = jnp.clip(slots, 0, num_hot - 1)
        is_hot = jnp.logical_and(slots >= 0, slots < num_hot)
        gathered = jnp.where(is_hot[..., None],
                             jnp.take(hot, hot_slot, axis=0), gathered)
    if weights is not None:
        w = jnp.asarray(weights)
        gathered = gathered * w[..., None].astype(gathered.dtype)
    out = gathered.sum(axis=1)
    if mode == "mean":
        if weights is not None:
            denom = jnp.maximum(w.sum(axis=1), 1e-9)[..., None]
        else:
            denom = jnp.asarray(slots.shape[1], dtype=out.dtype)
        out = out / denom
    elif mode != "sum":
        raise ValueError(f"unknown mode {mode!r}")
    return out


def _miss_list_from_slots(slots: np.ndarray,
                          rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """The miss-list: (sorted distinct rows, ascending flat occurrence
    positions) of the slot==MISS entries. PAD entries are silent."""
    flat_slots = np.asarray(slots).ravel()
    flat_rows = np.asarray(rows).ravel()
    pos = np.flatnonzero(flat_slots == MISS).astype(np.int32)
    if pos.size == 0:
        return np.empty(0, np.int32), pos
    return np.unique(flat_rows[pos]).astype(np.int32), pos


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def fused_warm_lookup(cache, slots, rows, weights=None, hot=None, *,
                      mode: str = "sum", backend: str = "auto",
                      opts: FusedLookupOpts | None = None
                      ) -> FusedLookupResult:
    """Fused warm-cache lookup: [C,D] x slot-map [B,L] -> FusedLookupResult.

    See the module docstring for the slot-map convention and the
    zero-contribution / miss-list contract. `backend` mirrors ops.py:
    'pallas' runs the TPU kernel (interpret=True automatically off-TPU),
    'xla' the eager reference composition, 'auto' picks per platform.
    Both backends return identical values and miss-lists.
    """
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "xla"
    slots_np = np.asarray(slots)
    rows_np = np.asarray(rows)
    batch, pooling = slots_np.shape
    cache = jnp.asarray(cache)
    if cache.shape[0] == 0:
        # zero-capacity cache: keep a 1-row dummy so the kernel/gather has
        # a well-formed operand; no slot can ever address it
        cache = jnp.zeros((1, cache.shape[1]), cache.dtype)
    if pooling == 0:
        # empty bags: the reference formula on an empty gather (sum -> 0,
        # unweighted mean -> 0/0) with no misses to report
        pooled = ref.embedding_bag_ref(
            jnp.zeros((1, cache.shape[1]), cache.dtype),
            jnp.zeros((batch, 0), jnp.int32),
            None if weights is None else jnp.asarray(weights), mode=mode)
        return FusedLookupResult(pooled, np.empty(0, np.int32),
                                 np.empty(0, np.int32))

    miss_rows, miss_pos = _miss_list_from_slots(slots_np, rows_np)
    if backend == "xla":
        pooled = fused_warm_lookup_xla(
            cache, slots_np, rows_np,
            None if weights is None else jnp.asarray(weights),
            None if hot is None else jnp.asarray(hot), mode=mode)
        return FusedLookupResult(pooled, miss_rows, miss_pos)
    if backend != "pallas":
        raise ValueError(f"unknown backend {backend!r}")

    opts = opts or FusedLookupOpts()
    if not _on_tpu() and not opts.interpret:
        opts = dataclasses.replace(opts, interpret=True)
    bb = opts.batch_block
    pad = (-batch) % bb
    if pad:
        # dummy bags carry the PAD sentinel: zero contribution, sliced
        # off below
        slots_np = np.concatenate(
            [slots_np, np.full((pad, pooling), PAD, slots_np.dtype)])
    w = None
    if weights is not None:
        w = jnp.asarray(weights)
        if pad:
            w = jnp.concatenate(
                [w, jnp.zeros((pad, pooling), w.dtype)], axis=0)
    pooled = fused_warm_lookup_pallas(
        cache, jnp.asarray(slots_np), w,
        None if hot is None else jnp.asarray(hot), opts=opts)
    pooled = pooled[:batch]
    # mean epilogue: eager, op-for-op the reference's division (runtime
    # scalar/vector operand — never an in-kernel constant, see docstring)
    if mode == "mean":
        if weights is not None:
            wsum = jnp.asarray(weights).sum(axis=1)
            pooled = pooled / jnp.maximum(wsum, 1e-9)[..., None]
        else:
            pooled = pooled / jnp.asarray(pooling, dtype=pooled.dtype)
    elif mode != "sum":
        raise ValueError(f"unknown mode {mode!r}")
    return FusedLookupResult(pooled, miss_rows, miss_pos)


def complete_miss_bags(pooled: jnp.ndarray, bag_ids: np.ndarray,
                       bag_rows, weights=None, *,
                       mode: str = "sum") -> jnp.ndarray:
    """Cold-path completion: RECOMPUTE miss-containing bags whole.

    pooled:   [B, D] the fused launch's partial output
    bag_ids:  [nb] bag indices that contained >= 1 miss
    bag_rows: [nb, L, D] the FULL row values for those bags, position
              order (hits re-read from any tier — all tiers hold identical
              bytes — misses from the cold gather)
    weights:  [B, L] (full batch; this helper slices) or None

    Adding cold rows to the partial sums would change summation order and
    drift 1 ULP; rebuilding the affected bags with the reference reduction
    shape keeps the completed output bit-exact with the dense reference.
    Runs eagerly — same reasoning as the xla variant.
    """
    bag_ids = np.asarray(bag_ids)
    if bag_ids.size == 0:
        return pooled
    rows = jnp.asarray(bag_rows)                               # [nb, L, D]
    w = None
    if weights is not None:
        w = jnp.asarray(weights)[jnp.asarray(bag_ids)]         # [nb, L]
        rows = rows * w[..., None].astype(rows.dtype)
    vals = rows.sum(axis=1)
    if mode == "mean":
        if w is not None:
            denom = jnp.maximum(w.sum(axis=1), 1e-9)[..., None]
        else:
            denom = jnp.asarray(rows.shape[1], dtype=vals.dtype)
        vals = vals / denom
    elif mode != "sum":
        raise ValueError(f"unknown mode {mode!r}")
    return pooled.at[jnp.asarray(bag_ids)].set(vals.astype(pooled.dtype))
