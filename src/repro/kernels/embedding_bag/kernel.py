"""Pallas TPU embedding-bag kernel: gather-reduce with software prefetching
and a VMEM-pinned hot-row cache.

TPU adaptation of the paper's three mechanisms (see DESIGN.md §2):

* software prefetching (paper §IV-B)  ->  index-driven `pltpu.make_async_copy`
  row DMAs from HBM into a rotating VMEM buffer, `prefetch_distance` rows in
  flight. Indices live in SMEM so the scalar core computes DMA addresses ahead
  of use — prefetches are 100% accurate, exactly as in the paper.
* L2 pinning (paper §IV-C)  ->  the hottest `num_hot` rows (tables stored
  hot-first, see core/hot_cache.py) are passed as a separate VMEM-resident
  operand; hot lookups never touch HBM.
* OptMT / occupancy (paper §III-C)  ->  `batch_block` (samples per grid step)
  and `prefetch_distance` control grid parallelism and DMA concurrency; the
  VMEM footprint of (pinned rows + pipeline buffers + output block) is the
  analogue of the register budget.

One launch serves a whole `[T, R, D]` table stack: the grid is
(table, batch block), the stack stays in HBM (`memory_space=ANY`) and each
row DMA addresses `table_ref.at[t, row]`. The indices, weights, hot block
and output are blocked per table. (A `jax.vmap` over a single-table
kernel cannot lower: Mosaic accepts an ANY-space operand only as one
whole, unblocked array.)

The pipeline is *flattened* over (sample, lookup) so row DMAs stream across
bag boundaries with no per-sample drain bubble — a beyond-paper improvement
(the paper's per-CUDA-thread pipeline restarts at each bag).

Layout notes (TPU): rows are [D] f32 with D a multiple of 128 preferred
(lane dimension). Tables must be float32: a packed dtype (bf16) stores
row pairs in one 32-bit word of an (8, 128) tile, and Mosaic refuses the
kernel's single-row slices of such a layout ("cannot statically prove
that index ... is a multiple of 8"). `embedding_bag_pallas` rejects such
tables up front.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


@dataclasses.dataclass(frozen=True)
class EmbeddingBagOpts:
    """Tuning knobs (paper-mechanism analogues)."""

    prefetch_distance: int = 8   # rows in flight (paper Fig. 9 sweep)
    batch_block: int = 8         # samples per grid step (occupancy analogue)
    num_hot: int = 0             # VMEM-pinned hot rows (L2P analogue); 0 = off
    mode: str = "sum"            # 'sum' | 'mean'
    interpret: bool = False      # CPU validation mode

    def vmem_bytes(self, dim: int, itemsize: int = 4) -> int:
        buf = self.prefetch_distance * dim * itemsize
        hot = self.num_hot * dim * itemsize
        out = self.batch_block * dim * itemsize
        return buf + hot + out


def _bag_kernel(idx_ref, w_ref, table_ref, hot_ref, out_ref, buf_ref, sem_ref,
                *, pooling: int, distance: int, num_hot: int, mode: str,
                has_weights: bool):
    """One grid step (t, b): `batch_block` bags of table t, flattened
    software pipeline.

    idx_ref: SMEM [batch_block, pooling] int32 (hot-first remapped)
    w_ref:   SMEM [batch_block, pooling] f32 or None
    table_ref: HBM [T, R, D] (memory_space=ANY; manual DMA only)
    hot_ref: VMEM [num_hot, D] or None
    out_ref: VMEM [batch_block, D]
    buf_ref: VMEM scratch [distance, D]
    sem_ref: DMA semaphores [distance]
    """
    tbl = pl.program_id(0)
    bb = out_ref.shape[0]
    dim = out_ref.shape[1]
    total = bb * pooling
    f32 = jnp.float32

    def row_dma(row, slot):
        return pltpu.make_async_copy(
            table_ref.at[tbl, row], buf_ref.at[slot],
            sem_ref.at[slot])

    def start_fetch(t):
        """Begin the HBM->VMEM row DMA for flat step t (cold rows only)."""
        row = idx_ref[t // pooling, t % pooling]

        @pl.when(row >= num_hot)
        def _():
            row_dma(row, jax.lax.rem(t, distance)).start()

    # Prologue: fill the pipeline `distance` deep (paper: prefetch distance).
    for j in range(min(distance, total)):
        start_fetch(j)

    def body(t, carry):
        acc, wsum = carry
        s = t // pooling
        i = t % pooling
        row = idx_ref[s, i]
        slot = jax.lax.rem(t, distance)
        is_hot = row < num_hot

        # Reset accumulator at bag start.
        acc = jnp.where(i == 0, jnp.zeros_like(acc), acc)
        wsum = jnp.where(i == 0, jnp.zeros_like(wsum), wsum)

        # Consume: wait on the DMA for cold rows; hot rows read VMEM directly.
        @pl.when(jnp.logical_not(is_hot))
        def _():
            row_dma(row, slot).wait()

        row_vec = buf_ref[pl.ds(slot, 1), :]                   # [1, D]
        if num_hot > 0:
            safe = jnp.minimum(row, num_hot - 1)
            row_vec = jnp.where(is_hot, hot_ref[pl.ds(safe, 1), :], row_vec)
        row_vec = row_vec.astype(f32)

        if has_weights:
            w = w_ref[s, i].astype(f32)
            acc = acc + row_vec[0] * w
            wsum = wsum + w
        else:
            acc = acc + row_vec[0]
            wsum = wsum + 1.0

        # Keep the pipeline full: prefetch row t+distance.
        @pl.when(t + distance < total)
        def _():
            start_fetch(t + distance)

        # Bag boundary: reduce and store.
        @pl.when(i == pooling - 1)
        def _():
            if mode == "mean":
                denom = jnp.maximum(wsum, 1e-9) if has_weights else f32(pooling)
                val = acc / denom
            else:
                val = acc
            out_ref[pl.ds(s, 1), :] = val[None, :].astype(out_ref.dtype)

        return acc, wsum

    init = (jnp.zeros((dim,), f32), f32(0.0))
    jax.lax.fori_loop(0, total, body, init)


def embedding_bag_pallas(tables: jnp.ndarray, indices: jnp.ndarray,
                         weights: jnp.ndarray | None = None,
                         opts: EmbeddingBagOpts = EmbeddingBagOpts()) -> jnp.ndarray:
    """Fixed-pooling embedding bag over a table stack, one Pallas launch.

    tables:  [T, R, D] (if opts.num_hot > 0, each table must already be
             hot-first ordered and `indices` remapped — see
             core/hot_cache.HotPlan)
    indices: [T, B, L] int32, B % opts.batch_block == 0 (ops.py pads)
    weights: [T, B, L] or None
    returns: [T, B, D] float32
    """
    if tables.dtype != jnp.float32:
        raise ValueError(
            f"the Pallas embedding-bag kernel takes float32 tables, got "
            f"{tables.dtype}: Mosaic refuses the single-row slices of a "
            f"packed dtype (use backend='xla' for {tables.dtype} tables)")
    num_tables, batch, pooling = indices.shape
    dim = tables.shape[2]
    bb = opts.batch_block
    if batch % bb:
        raise ValueError(f"batch {batch} not divisible by batch_block {bb}")
    distance = max(1, min(opts.prefetch_distance, bb * pooling))
    num_hot = int(min(opts.num_hot, tables.shape[1]))
    has_weights = weights is not None

    kernel = functools.partial(
        _bag_kernel, pooling=pooling, distance=distance, num_hot=num_hot,
        mode=opts.mode, has_weights=has_weights)

    # per-table blocks; `None` squeezes the table axis out of the kernel view
    bag_spec = pl.BlockSpec((None, bb, pooling), lambda t, b: (t, b, 0),
                            memory_space=pltpu.SMEM)
    in_specs = [
        bag_spec,
        bag_spec if has_weights else None,
        pl.BlockSpec(memory_space=pl.ANY),  # table stack stays in HBM
        (pl.BlockSpec((None, num_hot, dim), lambda t, b: (t, 0, 0))
         if num_hot else None),
    ]
    inputs = [indices.astype(jnp.int32),
              weights.astype(jnp.float32) if has_weights else None,
              tables,
              tables[:, :num_hot] if num_hot else None]

    # Drop the unused operand slots (w/ matching kernel signature via wrapper).
    live = [i for i, s in enumerate(in_specs) if s is not None]

    def kernel_wrapper(*refs):
        args = [None, None, None, None]
        for j, i in enumerate(live):
            args[i] = refs[j]
        _out, _buf, _sem = refs[len(live):]
        kernel(args[0], args[1], args[2], args[3], _out, _buf, _sem)

    return pl.pallas_call(
        kernel_wrapper,
        grid=(num_tables, batch // bb),
        in_specs=[in_specs[i] for i in live],
        out_specs=pl.BlockSpec((None, bb, dim), lambda t, b: (t, b, 0)),
        out_shape=jax.ShapeDtypeStruct((num_tables, batch, dim), tables.dtype),
        scratch_shapes=[
            pltpu.VMEM((distance, dim), tables.dtype),  # DMA dst == src
            pltpu.SemaphoreType.DMA((distance,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=opts.interpret,
        name="embedding_bag",
    )(*[inputs[i] for i in live])
