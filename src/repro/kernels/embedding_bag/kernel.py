"""Pallas TPU embedding-bag kernel: a slab gather with whole-tile reduces.

TPU adaptation of the paper's mechanisms (see DESIGN.md §2):

* software prefetching (paper §IV-B)  ->  each grid step's rows are
  fetched into one VMEM *slab* `[batch_block, L_pad, D]` by index-driven
  `pltpu.make_async_copy` row DMAs, issued back to back with no wait
  between two starts. Indices live in SMEM so the scalar core computes
  every DMA address ahead of use — prefetches are 100% accurate, exactly
  as in the paper. The slab is double-buffered across grid steps: step b
  issues block b+1's rows before it waits for and reduces block b's, so
  a whole step's rows are in flight while the previous slab is summed.
* L2 pinning (paper §IV-C) has no part here: the kernel's pace is the
  scalar core's one DMA start per looked-up row, which a VMEM source
  costs as much as an HBM one, so every row comes from the table (a
  branch between the two sources made the kernel 1.8x slower on a v5e).
  `EmbeddingStageConfig.pinned_rows` still stores tables hot-first.
* OptMT / occupancy (paper §III-C)  ->  `batch_block` (bags per grid step)
  sizes the slab; the VMEM footprint of (two slabs + output block) is the
  analogue of the register budget, and the wrapper lowers the bags per
  step from the shapes when it would not fit `VMEM_BUDGET`.

The reduce is `sum(slab[s, :L], axis=0)` over whole (8, 128) tiles, eight
rows per vector add; weights scale each slab row in place before the sum
(a multiply fused into the reduce would contract to an FMA), and `mean`
divides the finished sum.

One launch serves all tables, in one of two forms chosen by the caller's
layouts (storage/device.py):

* `embedding_bag_pallas`, tables of equal rows and bags of equal size: a
  `[T, R, D]` stack and `[T, B, L]` indices. The grid is (table, batch
  block) and each row DMA addresses `table_ref.at[t, row]`. The indices,
  weights and output are blocked per table. The table axis is `parallel`
  (a table's first step issues its own rows).
* `embedding_bag_flat_pallas`, rows and a bag size per table: one
  `[sum(R), D]` table with a static first row per table, and the flat
  `[B, sum(L)]` indices, table t's bag in its own static columns. The
  grid is the batch blocks alone; a step issues every bag of each of its
  queries as straight-line DMA starts (no bag is padded to the longest),
  puts table t's rows at its own 8-aligned place in the query's slab, and
  writes the `[bb, T, D]` pooled block. Rows are addressed as row ids
  (int32), never as flattened element offsets.

Equal tables and bags keep the stacked form: on a v5e at 32 tables of
500,000 rows and bags of 150, the flat form took 15.35 ns a row against
the stacked form's 13.83 (its issue loop is 4,800 straight-line DMA
starts a query, 11.5 MB of code against 0.44 MB).

In both the stack stays in HBM (`memory_space=ANY`), and the indices come
twice, as block b and as block b+1, so that a step can issue its
successor's rows. The batch axis carries the slab from one step to the
next, so it is `arbitrary`. (A `jax.vmap` over a single-table kernel
cannot lower: Mosaic accepts an ANY-space operand only as one whole,
unblocked array.)

Layout notes (TPU): rows are [D] f32 with D a multiple of 128 preferred
(lane dimension). Tables must be float32: a packed dtype (bf16) stores
row pairs in one 32-bit word of an (8, 128) tile, and Mosaic refuses the
kernel's single-row DMA destinations in such a layout ("cannot statically
prove that index ... is a multiple of 8"). `embedding_bag_pallas` rejects
such tables up front.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: VMEM the kernel's blocks may take: under v5e's 16 MiB default scoped
#: limit, with room left for Mosaic's own scratch.
VMEM_BUDGET = 12 * 2**20

#: most DMA starts per iteration of the scalar issue loop: a bag of up to
#: this many rows is issued as straight-line code (Mosaic lowers a
#: `fori_loop` only fully unrolled or not at all, so the kernel unrolls
#: by hand). On a v5e, a bag of 150 rows issued whole took 13.8 ns per
#: row; in chunks of 64 rows, 17.3.
UNROLL = 256

SUBLANES = 8   # float32 rows per (8, 128) tile


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@dataclasses.dataclass(frozen=True)
class EmbeddingBagOpts:
    """Tuning knobs (paper-mechanism analogues). The rows in flight are
    not a knob: a grid step keeps all of its successor's rows (up to
    batch_block x pooling) in flight."""

    # most bags per grid step, the slab's depth (occupancy analogue);
    # lowered to a divisor that fits VMEM_BUDGET
    batch_block: int = 8
    mode: str = "sum"            # 'sum' | 'mean'
    interpret: bool = False      # CPU validation mode

    def vmem_bytes(self, pooling, dim: int, itemsize: int = 4) -> int:
        """VMEM of the bag kernel's blocks: two slabs, and the pipeline's
        two buffers of the output block. `pooling` one int (the stacked
        kernel: a step's unit is a bag) or a tuple of one bag size per
        table (the flat kernel: a step's unit is a query, all its bags)."""
        row = dim * itemsize
        slab = 2 * self.batch_block * _slab_rows(pooling) * row
        out_rows = 1 if isinstance(pooling, int) else _round_up(
            len(pooling), SUBLANES)
        return slab + 2 * self.batch_block * out_rows * row


def _slab_rows(pooling) -> int:
    """Slab rows of one step's unit: each bag at an 8-aligned start."""
    bags = (pooling,) if isinstance(pooling, int) else pooling
    return sum(_round_up(max(1, n), SUBLANES) for n in bags)


def bags_per_step(opts: EmbeddingBagOpts, pooling, dim: int,
                  itemsize: int = 4) -> int:
    """The largest divisor of `opts.batch_block` whose blocks fit
    `VMEM_BUDGET` (1 if none does: the compiler then says what is over)."""
    bb = opts.batch_block
    for d in range(bb, 0, -1):
        if bb % d == 0 and dataclasses.replace(opts, batch_block=d).vmem_bytes(
                pooling, dim, itemsize) <= VMEM_BUDGET:
            return d
    return 1


def _bag_kernel(idx_ref, nxt_ref, w_ref, table_ref, out_ref, slab_ref,
                sem_ref, *, pooling: int, mode: str):
    """One grid step (t, b): issue block b+1's rows into one slab, then
    wait for block b's slab and reduce it.

    idx_ref:  SMEM [bb, L] int32, block b
    nxt_ref:  SMEM [bb, L] int32, block b+1 (block b at the last step)
    w_ref:    SMEM [bb, L] f32 or None
    table_ref: HBM [T, R, D] (memory_space=ANY; manual DMA only)
    out_ref:  VMEM [bb, D]
    slab_ref: VMEM scratch [2, bb, L_pad, D], one slab per block in flight
    sem_ref:  DMA semaphores [2], one per slab; every row of a slab
              signals it with one row's bytes
    """
    tbl = pl.program_id(0)
    blk = pl.program_id(1)
    bb = out_ref.shape[0]
    slot = jax.lax.rem(blk, 2)
    f32 = jnp.float32

    def issue(ids_ref, dst):
        """Start the DMA of every row of one block into slab `dst`, with
        no wait and no vector work between two starts."""
        sem = sem_ref.at[dst]

        def bag(s, c):
            def row(i):
                pltpu.make_async_copy(table_ref.at[tbl, ids_ref[s, i]],
                                      slab_ref.at[dst, s, i], sem).start()
            _issue_bag(row, pooling)
            return c
        jax.lax.fori_loop(0, bb, bag, 0)

    @pl.when(blk == 0)
    def _():
        issue(idx_ref, slot)

    @pl.when(blk + 1 < pl.num_programs(1))
    def _():
        issue(nxt_ref, 1 - slot)

    # Wait for block b's slab, every row issued before the first wait: a
    # wait takes one bag's bytes (L rows) off the slab's semaphore, which
    # counts the bytes landed whatever bag they belong to. The descriptor
    # only sizes the wait; it moves nothing, and it names the slab alone,
    # so that a table of fewer than L rows sizes it the same.
    bag_rows = slab_ref.at[slot, 0, pl.ds(0, pooling)]
    one_bag = pltpu.make_async_copy(bag_rows, bag_rows, sem_ref.at[slot])
    for _ in range(bb):
        one_bag.wait()

    def reduce(s, c):
        if w_ref is not None:
            def scale(i, c):
                # in place: the product is rounded before the sum reads it
                slab_ref[slot, s, pl.ds(i, 1), :] = (
                    slab_ref[slot, s, pl.ds(i, 1), :] * w_ref[s, i])
                return c
            jax.lax.fori_loop(0, pooling, scale, 0)
        val = jnp.sum(slab_ref[slot, s, pl.ds(0, pooling), :], axis=0)
        if mode == "mean":
            if w_ref is not None:
                wsum = jax.lax.fori_loop(
                    0, pooling, lambda i, a: a + w_ref[s, i], f32(0.0))
                val = val / jnp.maximum(wsum, 1e-9)
            else:
                val = val / f32(pooling)
        out_ref[pl.ds(s, 1), :] = val[None, :]
        return c
    jax.lax.fori_loop(0, bb, reduce, 0)


def _issue_bag(row, pooling: int) -> None:
    """Call `row(i)` for i in [0, pooling): straight-line code for bags
    under UNROLL rows, else a loop over UNROLL-row chunks and the rest
    straight-line."""
    def chunk(j, c):
        for u in range(UNROLL):
            row(j * UNROLL + u)
        return c
    if pooling >= UNROLL:
        jax.lax.fori_loop(0, pooling // UNROLL, chunk, 0)
    for i in range(pooling - pooling % UNROLL, pooling):
        row(i)


def _check_float32(tables: jnp.ndarray) -> None:
    if tables.dtype != jnp.float32:
        raise ValueError(
            f"the Pallas embedding-bag kernel takes float32 tables, got "
            f"{tables.dtype}: Mosaic refuses the single-row slices of a "
            f"packed dtype (use backend='xla' for {tables.dtype} tables)")


def embedding_bag_pallas(tables: jnp.ndarray, indices: jnp.ndarray,
                         weights: jnp.ndarray | None = None,
                         opts: EmbeddingBagOpts = EmbeddingBagOpts()) -> jnp.ndarray:
    """Fixed-pooling embedding bag over a table stack, one Pallas launch.

    tables:  [T, R, D]
    indices: [T, B, L] int32, B % opts.batch_block == 0 (ops.py pads)
    weights: [T, B, L] or None
    returns: [T, B, D] float32
    """
    _check_float32(tables)
    num_tables, batch, pooling = indices.shape
    dim = tables.shape[2]
    if batch % opts.batch_block:
        raise ValueError(f"batch {batch} not divisible by batch_block "
                         f"{opts.batch_block}")
    bb = bags_per_step(opts, pooling, dim, tables.dtype.itemsize)
    blocks = batch // bb
    has_weights = weights is not None

    kernel = functools.partial(_bag_kernel, pooling=pooling, mode=opts.mode)

    # Blocks of `bb` bags, as [T, B / bb, bb, ...] views: the last two
    # dims of a block are then whole, so `bb` need not be a multiple of 8
    # (the views are free when it is). `None` squeezes an axis out of the
    # kernel view.
    def bag_spec(index_map):
        return pl.BlockSpec((None, None, bb, pooling), index_map,
                            memory_space=pltpu.SMEM)
    this_block = bag_spec(lambda t, b: (t, b, 0, 0))
    next_block = bag_spec(lambda t, b: (t, jnp.minimum(b + 1, blocks - 1),
                                        0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)  # table stack stays in HBM
    blocked = (num_tables, blocks, bb, pooling)
    idx = indices.astype(jnp.int32).reshape(blocked)
    if has_weights:
        in_specs = [this_block, next_block, this_block, hbm]
        operands = (idx, idx, weights.astype(jnp.float32).reshape(blocked),
                    tables)
        body = kernel
    else:
        in_specs = [this_block, next_block, hbm]
        operands = (idx, idx, tables)

        def body(idx_ref, nxt_ref, *refs):
            kernel(idx_ref, nxt_ref, None, *refs)

    return pl.pallas_call(
        body,
        grid=(num_tables, blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, None, bb, dim),
                               lambda t, b: (t, b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_tables, blocks, bb, dim),
                                       tables.dtype),
        scratch_shapes=[
            # DMA dst == src dtype
            pltpu.VMEM((2, bb, _round_up(pooling, SUBLANES), dim),
                       tables.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=opts.interpret,
        name="embedding_bag",
    )(*operands).reshape(num_tables, batch, dim)


def _flat_kernel(idx_ref, nxt_ref, w_ref, table_ref, out_ref, slab_ref,
                 sem_ref, *, first_rows: tuple, pooling: tuple, mode: str):
    """One grid step b: issue block b+1's queries into one slab, then wait
    for block b's slab and reduce each query's bags.

    idx_ref:  SMEM [bb, sum(L)] int32, block b; table t's bag in columns
              cols[t]:cols[t + 1]
    nxt_ref:  SMEM [bb, sum(L)] int32, block b+1 (block b at the last step)
    w_ref:    SMEM [bb, sum(L)] f32 or None
    table_ref: HBM [sum(R), D]; table t's row r is row first_rows[t] + r
    out_ref:  VMEM [bb, T, D]
    slab_ref: VMEM scratch [2, bb, S, D]; table t's bag of query s in rows
              segs[t]:segs[t] + L_t of slab[., s]
    sem_ref:  DMA semaphores [2], one per slab
    """
    blk = pl.program_id(0)
    bb = out_ref.shape[0]
    slot = jax.lax.rem(blk, 2)
    f32 = jnp.float32
    cols = [0]
    segs = [0]
    for n in pooling:
        cols.append(cols[-1] + n)
        segs.append(segs[-1] + _round_up(max(1, n), SUBLANES))
    width = cols[-1]

    def issue(ids_ref, dst):
        """Start the DMA of every row of every bag of one block's queries
        into slab `dst`, with no wait and no vector work between two
        starts."""
        sem = sem_ref.at[dst]

        def query(s, c):
            for t, n in enumerate(pooling):
                def row(i, t=t):
                    r = ids_ref[s, cols[t] + i]
                    if first_rows[t]:
                        r = r + first_rows[t]
                    pltpu.make_async_copy(table_ref.at[r],
                                          slab_ref.at[dst, s, segs[t] + i],
                                          sem).start()
                _issue_bag(row, n)
            return c
        jax.lax.fori_loop(0, bb, query, 0)

    @pl.when(blk == 0)
    def _():
        issue(idx_ref, slot)

    @pl.when(blk + 1 < pl.num_programs(0))
    def _():
        issue(nxt_ref, 1 - slot)

    # Wait for block b's slab: each wait takes one query's bytes (sum(L)
    # rows) off the slab's semaphore. The descriptor only sizes the wait.
    query_rows = slab_ref.at[slot, 0, pl.ds(0, width)]
    one_query = pltpu.make_async_copy(query_rows, query_rows,
                                      sem_ref.at[slot])
    for _ in range(bb):
        one_query.wait()

    def reduce(s, c):
        for t, n in enumerate(pooling):
            seg, col = segs[t], cols[t]
            if w_ref is not None:
                def scale(i, c, seg=seg, col=col):
                    # in place: the product is rounded before the sum
                    slab_ref[slot, s, pl.ds(seg + i, 1), :] = (
                        slab_ref[slot, s, pl.ds(seg + i, 1), :]
                        * w_ref[s, col + i])
                    return c
                jax.lax.fori_loop(0, n, scale, 0)
            val = jnp.sum(slab_ref[slot, s, pl.ds(seg, n), :], axis=0)
            if mode == "mean":
                if w_ref is not None:
                    wsum = jax.lax.fori_loop(
                        0, n, lambda i, a, col=col: a + w_ref[s, col + i],
                        f32(0.0))
                    val = val / jnp.maximum(wsum, 1e-9)
                else:
                    val = val / f32(n)
            out_ref[s, pl.ds(t, 1), :] = val[None, :]
        return c
    jax.lax.fori_loop(0, bb, reduce, 0)


def embedding_bag_flat_pallas(table: jnp.ndarray, indices: jnp.ndarray,
                              weights: jnp.ndarray | None = None, *,
                              first_rows: tuple, pooling: tuple,
                              opts: EmbeddingBagOpts = EmbeddingBagOpts()
                              ) -> jnp.ndarray:
    """Embedding bags of a bag size per table over one table array, one
    Pallas launch.

    table:      [sum(R), D]; table t starts at row first_rows[t]
    indices:    [B, sum(pooling)] int32, table t's bag in columns
                sum(pooling[:t]):sum(pooling[:t + 1]), ids local to the
                table; B % opts.batch_block == 0 (ops.py pads)
    weights:    [B, sum(pooling)] or None
    first_rows: T static ints
    pooling:    T static bag sizes, each >= 1
    returns:    [B, T, D] float32
    """
    _check_float32(table)
    first_rows = tuple(int(r) for r in first_rows)
    pooling = tuple(int(n) for n in pooling)
    if len(first_rows) != len(pooling) or min(pooling) < 1:
        raise ValueError(f"one first row and one bag size >= 1 per table, "
                         f"got {first_rows} and {pooling}")
    batch, width = indices.shape
    if width != sum(pooling):
        raise ValueError(f"indices hold {width} columns, the bags "
                         f"{sum(pooling)}")
    if batch % opts.batch_block:
        raise ValueError(f"batch {batch} not divisible by batch_block "
                         f"{opts.batch_block}")
    num_tables, dim = len(pooling), table.shape[1]
    bb = bags_per_step(opts, pooling, dim, table.dtype.itemsize)
    blocks = batch // bb
    kernel = functools.partial(_flat_kernel, first_rows=first_rows,
                               pooling=pooling, mode=opts.mode)

    def query_spec(index_map):
        return pl.BlockSpec((None, bb, width), index_map,
                            memory_space=pltpu.SMEM)
    this_block = query_spec(lambda b: (b, 0, 0))
    next_block = query_spec(lambda b: (jnp.minimum(b + 1, blocks - 1), 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    blocked = (blocks, bb, width)
    idx = indices.astype(jnp.int32).reshape(blocked)
    if weights is not None:
        in_specs = [this_block, next_block, this_block, hbm]
        operands = (idx, idx, weights.astype(jnp.float32).reshape(blocked),
                    table)
        body = kernel
    else:
        in_specs = [this_block, next_block, hbm]
        operands = (idx, idx, table)

        def body(idx_ref, nxt_ref, *refs):
            kernel(idx_ref, nxt_ref, None, *refs)

    return pl.pallas_call(
        body,
        grid=(blocks,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bb, num_tables, dim), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, num_tables, dim),
                                       table.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, bb, _slab_rows(pooling), dim), table.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=opts.interpret,
        name="embedding_bag",
    )(*operands)
