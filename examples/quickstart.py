"""Quickstart: the paper's technique in 30 lines.

Builds a small embedding stage, profiles a trace, lets the planner size
the bag kernel's slab (bags per grid step within VMEM), and runs the
Pallas slab-gather lookups, which match the off-the-shelf XLA gather.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (EmbeddingBagCollection, EmbeddingStageConfig,
                        make_pattern, plan_embedding_stage)

ROWS, DIM, TABLES, POOL, BATCH = 20_000, 128, 4, 16, 64

# 1. a production-like skewed access trace (paper §III-B "high hot")
pattern = make_pattern("high_hot", ROWS, seed=0)
trace = pattern.sample(BATCH, POOL, seed=0)

# 2. the static profiling framework (paper §VII) picks the knobs
report = plan_embedding_stage(trace, ROWS, DIM)
print(f"planner: {report.hotness_unique_pct:.1f}% unique rows, "
      f"{report.batch_block} bags per grid step, "
      f"{report.vmem_bytes / 2**20:.2f} MiB of VMEM")

# 3. baseline collection (off-the-shelf XLA gather)
base_cfg = EmbeddingStageConfig(num_tables=TABLES, rows=ROWS, dim=DIM,
                                pooling=POOL, backend="xla")
ebc = EmbeddingBagCollection(base_cfg)
params = ebc.init(jax.random.PRNGKey(0))
indices = jnp.asarray(np.stack(
    [pattern.sample(BATCH, POOL, seed=t) for t in range(TABLES)], axis=1))
baseline = ebc.apply(params, indices)

# 4. the Pallas slab gather with the planner's knobs
opt_cfg = EmbeddingStageConfig(
    num_tables=TABLES, rows=ROWS, dim=DIM, pooling=POOL,
    backend="pallas",                       # interpret=True on CPU
    batch_block=report.batch_block)
optimized = EmbeddingBagCollection(opt_cfg).apply(params, indices)

err = float(jnp.abs(optimized - baseline).max())
print(f"slab-gather output matches baseline: max|err| = {err:.2e}")
assert err < 1e-4
print("OK")
