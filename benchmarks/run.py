"""Benchmark harness — one function per paper table/figure.

Output: ``name,us_per_call,derived`` CSV rows.
  us_per_call — wall-clock on this host's XLA CPU backend (relative hotness /
                pinning effects are real: host caches see the same locality).
  derived     — TPU-v5e modeled value from benchmarks/tpu_model.py or an
                exact dataset statistic (hit rates, coverage, unique%).

Scaled-down workload (CPU-feasible) unless noted; the paper's widths
(B=2048, pool 150, D=128) run on the chip through `bench/run.py`.

CLI: ``--sweep NAME`` (repeatable) runs a subset; ``--backend
{device,tiered,sharded,...}`` routes the `storage_backends` sweep through
the `repro.storage` registry for that backend only (default: every
registered backend). ``--json PATH`` additionally writes every emitted
value as a structured record ``{sweep, name, metric, value, units}``
(schema_version 1) — the stable surface `tools/check_bench.py` guards in
CI and future BENCH_*.json trajectory tracking consumes. The human CSV
lines are unchanged. Existing sweep names are unchanged.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

# support direct script runs (`python benchmarks/run.py`): python puts
# benchmarks/ on sys.path, but the imports need the repo root (for
# `benchmarks.tpu_model`) and src/ (for `repro`, when PYTHONPATH is unset)
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)

from repro.core import (EmbeddingBagCollection, EmbeddingStageConfig,
                        coverage_curve, hot_coverage, make_pattern,
                        plan_from_trace, unique_access_pct)
from repro.data.pipeline import HETERO_MIXES
from repro.models.dlrm import DLRM, DLRMConfig
from repro.utils import enable_compile_cache, timeit_median

from benchmarks.tpu_model import EmbedKernelModel

# scaled reference workload for CPU measurements
ROWS, DIM, BATCH, POOL, TABLES = 50_000, 128, 2048, 20, 8
HOTNESS = ("one_item", "high_hot", "med_hot", "low_hot", "random")
PIN_K = 6000   # VMEM budget analogue of the paper's 60K-rows-in-30MB L2
ROWS_CSV: list[str] = []
# structured records for --json (schema_version 1); emit() appends one
# record per metric it can parse out of a row
JSON_RECORDS: list[dict] = []
_CURRENT_SWEEP: str = ""
# global seed offset (--seed). Default 0 keeps every sweep byte-identical
# to the checked-in baseline; any other value shifts every pattern/rng/key
# seed in lockstep so a full run can be reproduced from the JSON header.
SEED = 0


def seeded(s: int) -> int:
    """Offset a sweep-local literal seed by the global --seed."""
    return SEED + s


def _coerce(v: str):
    if v in ("True", "False"):
        return v == "True"
    try:
        return float(v)
    except ValueError:
        return v


def _units_for(metric: str) -> str:
    if metric == "us_per_call" or metric.endswith("_us"):
        return "us"
    if metric.endswith("_ms"):
        return "ms"
    if metric.endswith("_s"):
        return "s"
    return ""


def _record(name: str, metric: str, value) -> None:
    JSON_RECORDS.append({"sweep": _CURRENT_SWEEP, "name": name,
                         "metric": metric, "value": value,
                         "units": _units_for(metric)})


def emit(name: str, us_per_call: float | str, derived: float | str):
    """Print one human CSV row (unchanged format) and mirror it into the
    structured JSON records: `us_per_call` becomes one record, a numeric
    `derived` one `derived` record, and a ``k=v k=v ...`` string one
    record per pair."""
    row = f"{name},{us_per_call},{derived}"
    ROWS_CSV.append(row)
    print(row, flush=True)
    if us_per_call != "":
        _record(name, "us_per_call", float(us_per_call))
    if isinstance(derived, str):
        pairs = [p.split("=", 1) for p in derived.split() if "=" in p]
        for k, v in pairs:
            _record(name, k, _coerce(v))
        if derived != "" and not pairs:
            _record(name, "derived", _coerce(derived))
    elif derived != "":
        _record(name, "derived", float(derived))


def _dlrm(backend="xla", pinned=0, plans=None) -> tuple[DLRM, dict]:
    cfg = DLRMConfig(embedding=EmbeddingStageConfig(
        num_tables=TABLES, rows=ROWS, dim=DIM, pooling=POOL,
        backend=backend, pinned_rows=pinned))
    model = DLRM(cfg, plans)
    params = model.init(jax.random.PRNGKey(SEED))
    return model, params


def _indices(hotness: str, seed=0) -> np.ndarray:
    pat = make_pattern(hotness, ROWS, seed=seeded(seed))
    return np.stack([pat.sample(BATCH, POOL, seed=seeded(seed) * 100 + t)
                     for t in range(TABLES)], axis=1)


def _hot_frac(hotness: str, k: int) -> float:
    """Hit rate of a cache planned on a *training* trace window, evaluated on
    a fresh window of the SAME distribution (the paper's offline profiling:
    same table, later traffic)."""
    if hotness == "one_item":
        return 1.0
    pat = make_pattern(hotness, ROWS, seed=seeded(0))  # fixed rank->row map
    train = pat.sample(BATCH, POOL, seed=seeded(0))
    plan = plan_from_trace(train, ROWS, k)
    evl = pat.sample(BATCH, POOL, seed=seeded(7))   # fresh traffic window
    return hot_coverage(evl, plan.perm[:k])


# ---------------------------------------------------------------------------

def tab3_unique_access():
    """At the paper's reference workload (500K rows, B=2048, pool 150)."""
    from repro.core.access_patterns import REF_ROWS
    for h in HOTNESS:
        pat = make_pattern(h, REF_ROWS, seed=seeded(0))
        got = unique_access_pct(pat.sample(2048, 150, seed=seeded(1)),
                                REF_ROWS)
        emit(f"tab3_unique_access/{h}", "", round(got, 4))


def fig5_coverage():
    from repro.core.access_patterns import REF_ROWS
    for h in HOTNESS:
        pat = make_pattern(h, REF_ROWS, seed=seeded(0))
        cov = coverage_curve(pat.sample(2048, 150, seed=seeded(1)))
        i = min(int(np.searchsorted(cov[:, 0], 10.0, side="left")),
                len(cov) - 1)
        emit(f"fig5_coverage_at_10pct_unique/{h}", "",
             round(float(cov[i, 1]), 2))


def fig1_embedding_contribution():
    model, params = _dlrm()
    fwd = jax.jit(lambda d, i: model.forward(params, d, i))
    emb = jax.jit(lambda i: model.embedding_only(params, i))
    dense = jnp.asarray(np.random.default_rng(SEED)
                        .standard_normal((BATCH, 13)).astype(np.float32))
    for h in HOTNESS:
        idx = jnp.asarray(_indices(h))
        t_e2e = timeit_median(lambda: fwd(dense, idx), iters=3, warmup=1)
        t_emb = timeit_median(lambda: emb(idx), iters=3, warmup=1)
        emit(f"fig1_e2e/{h}", round(t_e2e * 1e6, 1),
             f"emb_frac={t_emb / t_e2e:.2f}")


def fig6_pipeline_sweep():
    """OptMT analogue: modeled speedup vs pipeline depth (rows in flight)."""
    m = EmbedKernelModel(ROWS, DIM, BATCH, POOL)
    base = m.stage_time_s(hot_coverage=0.0, prefetch_distance=1,
                          num_tables=TABLES)
    for d in (1, 2, 4, 8, 16):
        t = m.stage_time_s(hot_coverage=0.0, prefetch_distance=d,
                           num_tables=TABLES)
        vmem_kib = (d * DIM * 4) / 1024  # spill-analogue: pipeline VMEM cost
        emit(f"fig6_depth{d}/cold", "",
             f"speedup={base / t:.3f} vmem_kib={vmem_kib:.1f}")


def fig9_prefetch_distance():
    """Modeled speedup over depth-2 baseline, per hotness (pinned cache on:
    hot lookups bypass the pipeline, shifting the optimal distance)."""
    m = EmbedKernelModel(ROWS, DIM, BATCH, POOL)
    for h in ("high_hot", "med_hot", "low_hot", "random"):
        cov = _hot_frac(h, PIN_K)
        base = m.stage_time_s(hot_coverage=cov, prefetch_distance=2,
                              num_tables=TABLES)
        for d in (1, 2, 4, 8, 10, 16):
            t = m.stage_time_s(hot_coverage=cov, prefetch_distance=d,
                               num_tables=TABLES)
            emit(f"fig9_dist{d}/{h}", "", round(base / t, 3))


def fig11_l2p_pooling():
    for pool in (10, 50, 150):
        m = EmbedKernelModel(ROWS, DIM, BATCH, pool)
        for h in ("high_hot", "med_hot"):
            cov = _hot_frac(h, PIN_K)
            t0 = m.stage_time_s(hot_coverage=0.0, prefetch_distance=8,
                                num_tables=TABLES)
            t1 = m.stage_time_s(hot_coverage=cov, prefetch_distance=8,
                                num_tables=TABLES)
            emit(f"fig11_pool{pool}/{h}", "", round(t0 / t1, 3))


def _schemes():
    """(name, hot_coverage_fn, distance) for the paper's design points."""
    return [
        ("base", lambda h: 0.0, 2),          # stock double-buffered pipeline
        ("optmt", lambda h: 0.0, 8),         # occupancy fix: deeper pipeline
        ("pf_optmt", lambda h: 0.0, 32),     # + software prefetching
        ("l2p_optmt", lambda h: _hot_frac(h, PIN_K), 8),      # + pinning
        ("pf_l2p_optmt", lambda h: _hot_frac(h, PIN_K), 32),  # combined
    ]


def fig12_embedding_speedup():
    m = EmbedKernelModel(ROWS, DIM, BATCH, POOL)
    base_t = m.stage_time_s(hot_coverage=0.0, prefetch_distance=2,
                            num_tables=TABLES)
    for name, covf, d in _schemes()[1:]:
        for h in ("high_hot", "med_hot", "low_hot", "random"):
            t = m.stage_time_s(hot_coverage=covf(h), prefetch_distance=d,
                               num_tables=TABLES)
            emit(f"fig12_{name}/{h}", "", round(base_t / t, 3))


def fig12_measured_cpu():
    """CPU-measurable slice of Fig. 12: hot-first table reordering improves
    host cache locality for the XLA gather (same mechanism, host LLC)."""
    model, params = _dlrm()
    emb = jax.jit(lambda i: model.embedding_only(params, i))
    for h in ("high_hot", "random"):
        idx_raw = _indices(h)
        t_base = timeit_median(lambda: emb(jnp.asarray(idx_raw)), iters=3,
                               warmup=1)
        plans = [plan_from_trace(idx_raw[:, t], ROWS, PIN_K)
                 for t in range(TABLES)]
        cfgp = EmbeddingStageConfig(num_tables=TABLES, rows=ROWS, dim=DIM,
                                    pooling=POOL, backend="xla",
                                    pinned_rows=PIN_K)
        ebcp = EmbeddingBagCollection(cfgp, plans)
        perm = jnp.asarray(np.stack([p.perm for p in plans]))
        tables_p = jax.vmap(lambda t, pm: jnp.take(t, pm, axis=0))(
            params["embedding"]["tables"], perm)
        embp = jax.jit(lambda i: ebcp.apply({"tables": tables_p}, i))
        idx = jnp.asarray(idx_raw)
        t_pin = timeit_median(lambda: embp(idx), iters=3, warmup=1)
        emit(f"fig12_measured_hotfirst/{h}", round(t_base * 1e6, 1),
             f"speedup={t_base / t_pin:.3f}")


def fig13_e2e_speedup():
    """End-to-end: embedding model + non-embedding compute (MXU model)."""
    m = EmbedKernelModel(ROWS, DIM, BATCH, POOL)
    mlp_flops = 2 * BATCH * (13 * 1024 + 1024 * 512 + 512 * 128 + 128 * 128)
    inter = TABLES + 1
    top_in = 128 + inter * (inter - 1) // 2
    mlp_flops += 2 * BATCH * (top_in * 128 + 128 * 64 + 64)
    t_ne = mlp_flops / (0.3 * 197e12)  # 30% MFU on the small GEMMs
    t_base = m.stage_time_s(hot_coverage=0.0, prefetch_distance=2,
                            num_tables=TABLES) + t_ne
    for name, covf, d in _schemes()[1:]:
        for h in ("high_hot", "med_hot", "low_hot", "random"):
            t1 = m.stage_time_s(hot_coverage=covf(h), prefetch_distance=d,
                                num_tables=TABLES) + t_ne
            emit(f"fig13_{name}/{h}", "", round(t_base / t1, 3))


def fig14_gap():
    """Fastest(one_item)-vs-slowest(random) gap closing."""
    m = EmbedKernelModel(ROWS, DIM, BATCH, POOL)
    for name, covf, d in _schemes():
        fast = m.stage_time_s(hot_coverage=1.0, prefetch_distance=d,
                              num_tables=TABLES)
        slow = m.stage_time_s(hot_coverage=covf("random"),
                              prefetch_distance=d, num_tables=TABLES)
        emit(f"fig14_gap/{name}", "", round(slow / fast, 2))


def fig15_buffer_schemes():
    """Buffer-station comparison -> depth sweep on TPU (stations collapse to
    VMEM; RPF/SMPF/LMPF differ only in achievable depth)."""
    m = EmbedKernelModel(ROWS, DIM, BATCH, POOL)
    base = m.stage_time_s(hot_coverage=0.0, prefetch_distance=1,
                          num_tables=TABLES)
    for d, tag in ((2, "rpf_like"), (8, "smpf_like"), (16, "lmpf_like")):
        t = m.stage_time_s(hot_coverage=0.0, prefetch_distance=d,
                           num_tables=TABLES)
        emit(f"fig15_{tag}_d{d}/random", "", round(base / t, 3))


def fig16_no_optmt():
    """Schemes without the occupancy knob (depth stays at base)."""
    m = EmbedKernelModel(ROWS, DIM, BATCH, POOL)
    base = m.stage_time_s(hot_coverage=0.0, prefetch_distance=1,
                          num_tables=TABLES)
    for h in ("high_hot", "random"):
        cov = _hot_frac(h, PIN_K)
        pf = m.stage_time_s(hot_coverage=0.0, prefetch_distance=10,
                            num_tables=TABLES)
        l2p = m.stage_time_s(hot_coverage=cov, prefetch_distance=1,
                             num_tables=TABLES)
        both = m.stage_time_s(hot_coverage=cov, prefetch_distance=10,
                              num_tables=TABLES)
        emit(f"fig16_pf/{h}", "", round(base / pf, 3))
        emit(f"fig16_l2p/{h}", "", round(base / l2p, 3))
        emit(f"fig16_both/{h}", "", round(base / both, 3))


def fig17_heterogeneous():
    m = EmbedKernelModel(ROWS, DIM, BATCH, POOL)
    for mix, counts in HETERO_MIXES.items():
        total = sum(counts.values())
        t0 = t1 = 0.0
        for h, n in counts.items():
            cov = _hot_frac(h, PIN_K)
            t0 += (n / total) * m.stage_time_s(hot_coverage=0.0,
                                               prefetch_distance=1,
                                               num_tables=TABLES)
            t1 += (n / total) * m.stage_time_s(hot_coverage=cov,
                                               prefetch_distance=16,
                                               num_tables=TABLES)
        emit(f"fig17_combined/{mix}", "", round(t0 / t1, 3))


def tab45_microarch():
    """Exact counters for the TPU kernel: hot-cache hit rate, HBM bytes,
    modeled BW utilization — analogues of the paper's NCU tables IV/V/VIII/IX
    (software-managed VMEM makes 'hit rates' exact, not sampled)."""
    m = EmbedKernelModel(ROWS, DIM, BATCH, POOL)
    for h in HOTNESS:
        cov = _hot_frac(h, PIN_K)
        emit(f"tab45_hot_hit_rate/{h}", "", round(cov, 4))
        emit(f"tab45_hbm_MB/{h}", "",
             round(m.hbm_bytes(hot_coverage=cov, num_tables=TABLES) / 1e6, 2))
        emit(f"tab45_bw_util/{h}", "",
             round(m.bandwidth_util(hot_coverage=cov, prefetch_distance=16,
                                    num_tables=TABLES), 4))


def tiered_ps_capacity_sweep():
    """Tiered parameter-server sweep (beyond-paper: beyond-HBM serving).

    Hot+warm device tiers sized as a fraction of total rows; cold tier in
    host memory. Reports exact hit/miss/eviction counters per HETERO_MIXES
    traffic mix and per hotness level — the serving-cache generalization of
    the paper's L2-pin (hot tier) + software prefetch (cold-tier staging).
    Scaled-down workload: table COUNTS from Table VII divided by 5.
    """
    from repro.ps import ParameterServer, PSConfig
    rows, batch, pool, dim = 2000, 256, 20, 8

    def run(hotness_list, frac):
        pats = [make_pattern(h, rows, seed=seeded(t))
                for t, h in enumerate(hotness_list)]
        t_count = len(pats)
        cap = int(frac * rows)
        cfg = PSConfig(hot_rows=cap // 2, warm_slots=cap - cap // 2,
                       prefetch_depth=2, window_batches=8)

        def mk(seed):
            return np.stack([p.sample(batch, pool, seed=seed * 100 + t)
                             for t, p in enumerate(pats)],
                            axis=1).astype(np.int32)
        trace = np.concatenate([mk(s) for s in range(2)], axis=0)
        ps = ParameterServer(np.zeros((t_count, rows, dim), np.float32),
                             cfg, trace=trace)
        for s in range(2, 4):                      # warmup
            ps.lookup(mk(s))
        ps.reset_stats()
        for s in range(4, 9):                      # measured
            ps.stage(mk(s + 1))                    # prefetch next batch
            ps.lookup(mk(s))
        return ps.stats()

    for h in ("high_hot", "med_hot", "low_hot", "random"):
        for frac in (0.05, 0.10, 0.20):
            st = run([h] * 4, frac)
            emit(f"tiered_ps_cap{int(frac*100)}pct/{h}", "",
                 f"hit={st['cache_hit_rate']:.3f} "
                 f"hot={st['hot_hit_rate']:.3f} "
                 f"warm={st['warm_hit_rate']:.3f} "
                 f"evict={st['evictions']} "
                 f"pf_hits={st['prefetch_hits']}")

    for mix, counts in HETERO_MIXES.items():
        hotness = []
        for h, n in counts.items():
            hotness += [h] * max(1, n // 5)
        for frac in (0.10, 0.20):
            st = run(hotness, frac)
            emit(f"tiered_ps_cap{int(frac*100)}pct/{mix}", "",
                 f"hit={st['cache_hit_rate']:.3f} "
                 f"cold_miss={st['cold_miss_rate']:.3f} "
                 f"evict={st['evictions']}")


def tiered_ps_sync_vs_async():
    """Sync vs async (threaded, double-buffered) prefetch staging.

    Runs identical traffic through both engines, verifies every lookup is
    bit-exact across modes, and reports the overlap stats the async path
    exists for: max queue depth, the fraction of cold-missed rows resolved
    off the critical path (`off_critical`), and — async only — how often
    the consumer found its double buffer already resolved (`overlap`) vs
    had to wait for / inline-resolve it (`waits`).
    """
    from repro.ps import ParameterServer, PSConfig
    rows, batch, pool, dim, t_count = 2000, 256, 20, 8, 4
    rng = np.random.default_rng(SEED)
    tables = rng.normal(size=(t_count, rows, dim)).astype(np.float32)

    def run(hotness, async_prefetch):
        pats = [make_pattern(hotness, rows, seed=seeded(t))
                for t in range(t_count)]

        def mk(seed):
            return np.stack([p.sample(batch, pool, seed=seed * 100 + t)
                             for t, p in enumerate(pats)],
                            axis=1).astype(np.int32)
        cfg = PSConfig(hot_rows=100, warm_slots=100, prefetch_depth=2,
                       async_prefetch=async_prefetch, window_batches=8)
        ps = ParameterServer(tables, cfg,
                             trace=np.concatenate([mk(s) for s in range(2)],
                                                  axis=0))
        outs = []
        for s in range(2, 10):
            ps.stage(mk(s + 1))                # overlap the next batch
            outs.append(ps.lookup(mk(s)))
            if s == 5:
                ps.refresh()                   # re-pin mid-stream
        st = ps.stats()
        ps.close()
        return np.stack(outs), st

    for h in ("med_hot", "random"):
        res = {m: run(h, m == "async") for m in ("sync", "async")}
        exact = bool(np.array_equal(res["sync"][0], res["async"][0]))
        for m, (_, st) in res.items():
            line = (f"bit_exact={exact} "
                    f"off_critical={st['off_critical_frac']:.3f} "
                    f"qdepth_max={st['max_queue_depth']}")
            if m == "async":
                line += (f" overlap={st['consume_overlap_frac']:.2f} "
                         f"waits={st['consume_waited']}")
            emit(f"tiered_ps_{m}_prefetch/{h}", "", line)


def tiered_ps_autotune():
    """Planner-driven tier sizing: `plan_tier_capacities()` splits a device
    byte budget into hot/warm capacities from the trace's coverage curve,
    then the planned config is measured on fresh traffic of the same
    distribution (achieved cache hit rate vs the planner's coverage bound).
    """
    from repro.core import plan_tier_capacities
    from repro.ps import ParameterServer, PSConfig
    rows, batch, pool, dim, t_count = 2000, 256, 20, 8, 4
    for h in ("high_hot", "med_hot", "low_hot"):
        pats = [make_pattern(h, rows, seed=seeded(t))
                for t in range(t_count)]

        def mk(seed):
            return np.stack([p.sample(batch, pool, seed=seed * 100 + t)
                             for t, p in enumerate(pats)],
                            axis=1).astype(np.int32)
        trace = np.concatenate([mk(s) for s in range(2)], axis=0)
        for budget_kib in (8, 32, 128):
            plan = plan_tier_capacities(trace, rows, dim,
                                        budget_kib * 1024)
            cfg = PSConfig.from_plan(plan, prefetch_depth=2)
            ps = ParameterServer(
                np.zeros((t_count, rows, dim), np.float32), cfg,
                trace=trace)
            for s in range(2, 4):                      # warmup
                ps.lookup(mk(s))
            ps.reset_stats()
            for s in range(4, 8):                      # measured
                ps.lookup(mk(s))
            st = ps.stats()
            emit(f"tiered_ps_autotune_kib{budget_kib}/{h}", "",
                 f"hot={plan.hot_rows} warm={plan.warm_slots} "
                 f"plan_cov={plan.total_coverage:.3f} "
                 f"hit={st['cache_hit_rate']:.3f}")


def storage_backends(backends: list[str] | None = None):
    """Serve identical traffic through every registered storage backend via
    `ServingSession` (the protocol path: registry -> backend -> generic
    overlap driver) and report bit-exactness vs the dense pooled reference
    plus the cache/overlap counters each backend surfaces. Tiny shapes:
    a CI-smoke-speed sweep (seconds), not a throughput measurement.
    """
    from repro import storage as storage_registry
    from repro.data import DLRMQueryStream
    from repro.ps import PSConfig
    from repro.serving import BatcherConfig, ServingSession
    backends = backends or storage_registry.available()
    rows, dim, batch, pool, t_count = 2000, 16, 32, 10, 4

    def mk_model(backend):
        cfg = DLRMConfig(embedding=EmbeddingStageConfig(
            num_tables=t_count, rows=rows, dim=dim, pooling=pool,
            backend="xla", storage=backend),
            bottom_mlp=(32, dim), top_mlp=(16, 1))
        return DLRM(cfg)

    ref_model = mk_model("device")
    params = ref_model.init(jax.random.PRNGKey(SEED))
    for backend in backends:
        for h in ("med_hot", "random"):
            stream = DLRMQueryStream(num_tables=t_count, rows=rows,
                                     pooling=pool, batch_size=batch,
                                     hotness=h, seed=seeded(0))
            model = mk_model(backend)
            store = model.ebc.storage
            caps = store.capabilities()
            if not caps.device_resident:
                build_kw = ({"num_shards": 2} if caps.shardable else {})
                store.build(params,
                            PSConfig(hot_rows=rows // 10,
                                     warm_slots=rows // 10,
                                     window_batches=8,
                                     async_prefetch=True),
                            trace=stream.sample_trace(2), **build_kw)
                caps = store.capabilities()   # staging caps appear on build
            # bit-exactness of the pooled embedding stage on one batch
            idx = jnp.asarray(stream.next_batch().indices)
            exact = bool(np.array_equal(
                np.asarray(model.embedding_only(params, idx)),
                np.asarray(ref_model.embedding_only(params, idx))))
            sess = ServingSession(
                model, params,
                batcher=BatcherConfig(max_batch=batch, max_wait_s=0.0),
                sla_ms=1e6,
                refresh_every_batches=4 if caps.refreshable else 0)
            for b in range(4):
                nb = stream.next_batch()
                sess.submit_batch(nb.dense, nb.indices, qid0=b * batch)
                if b >= 1:
                    sess.poll()
            sess.drain()
            sess.close()     # install any in-flight refresh before reading
            pct = sess.percentiles()
            line = (f"bit_exact={exact} served={pct['served']} "
                    f"caps={caps.describe()}")
            if "cache_hit_rate" in pct:
                line += (f" hit={pct['cache_hit_rate']:.3f}"
                         f" off_critical={pct['off_critical_frac']:.3f}")
            emit(f"storage_backend/{backend}/{h}", "", line)


def sharded_balance():
    """Frequency-aware table-to-shard placement on a skewed table mix:
    contiguous split vs the LPT-balanced planner (`plan_shard_placement`).
    Reports the cost-model imbalance ratio (max shard load / mean shard
    load — deterministic from the trace), bit-exactness vs the dense
    pooled reference, and session p99 latency. The heavy tables are
    deliberately stacked at one end of the table range so the contiguous
    split is maximally lopsided. Tiny shapes: CI-guard speed, not a
    throughput measurement.
    """
    from repro.ps import PSConfig
    from repro.serving import BatcherConfig, ServingSession
    from repro.storage import (ShardPlacement, estimate_table_loads,
                               plan_shard_placement)
    rows, dim, batch, pool = 2000, 16, 32, 10
    hotness = ("one_item", "one_item", "high_hot", "high_hot",
               "med_hot", "low_hot", "random", "random")
    t_count = len(hotness)
    pats = [make_pattern(h, rows, seed=seeded(t))
            for t, h in enumerate(hotness)]

    def mk(seed):
        return np.stack([p.sample(batch, pool, seed=seed * 100 + t)
                         for t, p in enumerate(pats)],
                        axis=1).astype(np.int32)

    trace = np.concatenate([mk(s) for s in range(2)], axis=0)
    row_bytes = dim * 4
    loads = estimate_table_loads(trace, row_bytes)
    placements = {
        "contiguous": ShardPlacement.contiguous(t_count, 2, loads=loads),
        "balanced": plan_shard_placement(trace, 2, row_bytes=row_bytes),
    }

    def mk_model(backend):
        cfg = DLRMConfig(embedding=EmbeddingStageConfig(
            num_tables=t_count, rows=rows, dim=dim, pooling=pool,
            backend="xla", storage=backend),
            bottom_mlp=(32, dim), top_mlp=(16, 1))
        return DLRM(cfg)

    ref_model = mk_model("device")
    params = ref_model.init(jax.random.PRNGKey(SEED))
    rng = np.random.default_rng(SEED)
    for pname, plc in placements.items():
        model = mk_model("sharded")
        model.ebc.storage.build(
            params,
            PSConfig(hot_rows=rows // 10, warm_slots=rows // 10,
                     window_batches=8, async_prefetch=True),
            trace=trace, placement=plc)
        idx = jnp.asarray(mk(7))
        exact = bool(np.array_equal(
            np.asarray(model.embedding_only(params, idx)),
            np.asarray(ref_model.embedding_only(params, idx))))
        sess = ServingSession(
            model, params,
            batcher=BatcherConfig(max_batch=batch, max_wait_s=0.0),
            sla_ms=1e6)
        for b in range(4):
            dense = rng.standard_normal(
                (batch, model.cfg.dense_features)).astype(np.float32)
            sess.submit_batch(dense, mk(b + 10), qid0=b * batch)
            if b >= 1:
                sess.poll()
        sess.drain()
        sess.close()
        pct = sess.percentiles()
        emit(f"sharded_balance/{pname}", "",
             f"imbalance={plc.imbalance_ratio():.4f} bit_exact={exact} "
             f"served={pct['served']} p99_ms={pct['p99_ms']:.2f}")


def sharded_migration():
    """Live placement: load-aware replica routing + mid-serving migration.

    Routing half — a replicated table with one synthetically slow replica
    (a per-row sleep models a contended shard). `route_equal` serves the
    legacy equal slices; `route_aware` lets the session auto-tuner fold
    observed per-replica service cost into the `ReplicaRouter` every 2
    batches, shifting the batch split off the slow copy. The bench-guard
    invariant: routed p99 below equal p99, and the slow replica's final
    batch share (`slow_frac`, deterministic up to EWMA of a ~100x injected
    cost gap) below the equal 0.5.

    Migration half — the skewed table mix from `sharded_balance` served on
    a contiguous placement with a migration threshold armed; the live
    window crosses it, `plan_migration`/`install_migration` swap the
    placement build-before-teardown mid-stream, and every batch before,
    during, and after the swap is checked bit-exact vs the dense gather
    (`bit_exact` is the hard CI record).
    """
    from repro.ps import AutoTuneConfig, PSConfig
    from repro.serving import BatcherConfig, ServingSession
    from repro.storage import ShardPlacement, estimate_table_loads
    rows, dim, batch, pool = 2000, 16, 32, 10

    def mk_model(backend, t_count):
        cfg = DLRMConfig(embedding=EmbeddingStageConfig(
            num_tables=t_count, rows=rows, dim=dim, pooling=pool,
            backend="xla", storage=backend),
            bottom_mlp=(32, dim), top_mlp=(16, 1))
        return DLRM(cfg)

    # -- routing: slow replica sheds load ---------------------------------
    hotness = ("random", "high_hot", "med_hot", "low_hot")
    t_count = len(hotness)
    pats = [make_pattern(h, rows, seed=seeded(t))
            for t, h in enumerate(hotness)]

    def mk(seed):
        return np.stack([p.sample(batch, pool, seed=seed * 100 + t)
                         for t, p in enumerate(pats)],
                        axis=1).astype(np.int32)

    trace = np.concatenate([mk(s) for s in range(2)], axis=0)
    loads = estimate_table_loads(trace, dim * 4)
    plc = ShardPlacement(num_tables=t_count, num_shards=2,
                         replicas=((0, 1), (0,), (1,), (1,)),
                         loads=tuple(float(x) for x in loads),
                         strategy="replicated")
    ref_model = mk_model("device", t_count)
    params = ref_model.init(jax.random.PRNGKey(SEED))
    rng = np.random.default_rng(SEED)
    for mode in ("equal", "aware"):
        model = mk_model("sharded", t_count)
        store = model.ebc.storage
        store.build(params,
                    PSConfig(hot_rows=rows // 10, warm_slots=rows // 10,
                             window_batches=8, async_prefetch=True),
                    trace=trace, placement=plc)
        # replica k=1 of the replicated table pays a per-row penalty
        slow = next(u for u in store._units
                    if u.chunk is not None and u.chunk[0] == 1)
        real_lookup = slow.ps.lookup
        slow.ps.lookup = lambda idx: (time.sleep(idx.shape[0] * 2e-3),
                                      real_lookup(idx))[1]
        t_rep = int(slow.table_ids[0])
        # converge the router BEFORE the measured window (in `aware` mode):
        # the p99 comparison is steady-state routing vs steady-state equal
        # slicing, not the one-window learning transient
        for step in range(6):
            model.embedding_only(params, jnp.asarray(mk(step + 30)))
            if mode == "aware" and step % 2 == 1:
                store.update_routing()
        tune = (AutoTuneConfig(depth_every_batches=0, route_every_batches=2)
                if mode == "aware" else None)
        sess = ServingSession(
            model, params,
            batcher=BatcherConfig(max_batch=batch, max_wait_s=0.0),
            sla_ms=1e6, auto_tune=tune)
        for b in range(8):
            dense = rng.standard_normal(
                (batch, model.cfg.dense_features)).astype(np.float32)
            sess.submit_batch(dense, mk(b + 10))
            if b >= 1:
                sess.poll()
        sess.drain()
        idx = jnp.asarray(mk(7))
        exact = bool(np.array_equal(
            np.asarray(model.embedding_only(params, idx)),
            np.asarray(ref_model.embedding_only(params, idx))))
        pct = sess.percentiles()
        slow_frac = float(store._routers[t_rep].fractions()[1])
        sess.close()
        emit(f"sharded_migration/route_{mode}", "",
             f"bit_exact={exact} served={pct['served']} "
             f"slow_frac={slow_frac:.4f} p99_ms={pct['p99_ms']:.2f} "
             f"mean_batch_ms={pct['mean_batch_ms']:.2f}")

    # -- migration: placement follows traffic drift, bit-exact ------------
    hotness = ("one_item", "one_item", "high_hot", "high_hot",
               "med_hot", "low_hot", "random", "random")
    t_count = len(hotness)
    pats = [make_pattern(h, rows, seed=seeded(t))
            for t, h in enumerate(hotness)]
    trace = np.concatenate([mk(s) for s in range(2)], axis=0)
    ref_model = mk_model("device", t_count)
    params = ref_model.init(jax.random.PRNGKey(SEED))
    model = mk_model("sharded", t_count)
    store = model.ebc.storage
    store.build(params,
                PSConfig(hot_rows=rows // 10, warm_slots=rows // 10,
                         window_batches=8, async_prefetch=True),
                trace=trace, num_shards=2, placement="contiguous",
                migration_threshold=1.1)

    def check(seed):
        idx = jnp.asarray(mk(seed))
        return bool(np.array_equal(
            np.asarray(model.embedding_only(params, idx)),
            np.asarray(ref_model.embedding_only(params, idx))))

    exact = all(check(s) for s in range(4))           # before (fills window)
    plan = store.plan_migration()
    exact &= check(4)                                 # during (plan pending)
    res = store.install_migration(plan) if plan else {"migrated": False}
    exact &= all(check(s) for s in range(5, 9))       # after the swap
    store.close()
    emit("sharded_migration/live_migration", "",
         f"bit_exact={exact} migrated={res.get('migrated', False)} "
         f"imb_before={res.get('imbalance_before', 0.0):.4f} "
         f"imb_after={res.get('imbalance_after', 0.0):.4f}")


def sharded_pool():
    """Process-pool sharded serving (`PoolStorage`: worker processes behind
    the framed pipe RPC, one shared host cold tier) vs the in-process
    thread-sharded backend.

    parity/    — the `sharded_balance` skewed mix on a balanced placement,
                 served by both backends through `ServingSession`. Hard
                 record: `bit_exact` (the RPC scatter/gather must reproduce
                 the thread path row-for-row); `p99_ms` rides the timing
                 band so pool work can't silently slow either path.

    host_tier/ — the shared-host-tier dedup claim, measured. The same
                 tables are built at 1/2/4 workers on placements whose
                 units are contiguous runs (including replicated tables at
                 W>=2): every worker serves zero-copy shm VIEWS, so
                 `resident_cold_bytes` must stay ONE table copy however
                 many processes map it — flat, not linear, in worker count
                 (a within-run `check_bench` invariant) — while
                 `host_view_bytes` (the sum of per-worker mapped views)
                 grows past one copy as replicas stack up.

    shift_*/   — a moving hot set: the shift trace's phase flip re-aimed at
                 the table axis (the row-level `make_traffic("shift")`
                 re-scatter moves rows WITHIN tables, which the table-load
                 cost model is invariant to by construction — so the bench
                 moves the per-table hotness mix instead). Phase A's skew
                 is served on a contiguous split with a migration threshold
                 armed; the live window trips it and the placement is
                 migrated mid-serving. Phase B then coalesces the hot set
                 onto the tables that landed together on shard 0 — the
                 worst drift for the installed placement at ANY seed — and
                 a second migration follows the hot set. Run on sharded AND
                 pool: records imbalance before/after each swap and
                 bit-exactness across every batch, including the
                 cross-process build-before-teardown commit.
    """
    from repro.ps import PSConfig
    from repro.serving import BatcherConfig, ServingSession
    from repro.storage import ShardPlacement, plan_shard_placement
    rows, dim, batch, pool = 2000, 16, 32, 10
    hotness = ("one_item", "one_item", "high_hot", "high_hot",
               "med_hot", "low_hot", "random", "random")
    t_count = len(hotness)

    def mk_pats(hot):
        return [make_pattern(h, rows, seed=seeded(t))
                for t, h in enumerate(hot)]

    def mk(pats, seed):
        return np.stack([p.sample(batch, pool, seed=seed * 100 + t)
                         for t, p in enumerate(pats)],
                        axis=1).astype(np.int32)

    def mk_model(backend):
        cfg = DLRMConfig(embedding=EmbeddingStageConfig(
            num_tables=t_count, rows=rows, dim=dim, pooling=pool,
            backend="xla", storage=backend),
            bottom_mlp=(32, dim), top_mlp=(16, 1))
        return DLRM(cfg)

    def ps_cfg():
        return PSConfig(hot_rows=rows // 10, warm_slots=rows // 10,
                        window_batches=8, async_prefetch=True)

    pats = mk_pats(hotness)
    trace = np.concatenate([mk(pats, s) for s in range(2)], axis=0)
    ref_model = mk_model("device")
    params = ref_model.init(jax.random.PRNGKey(SEED))
    rng = np.random.default_rng(SEED)

    # -- parity: same traffic, thread shards vs worker processes ----------
    balanced = plan_shard_placement(trace, 2, row_bytes=dim * 4)
    for backend in ("sharded", "pool"):
        model = mk_model(backend)
        store = model.ebc.storage
        build_kw = {"num_workers": 2} if backend == "pool" else {}
        store.build(params, ps_cfg(), trace=trace, placement=balanced,
                    **build_kw)
        idx = jnp.asarray(mk(pats, 7))
        exact = bool(np.array_equal(
            np.asarray(model.embedding_only(params, idx)),
            np.asarray(ref_model.embedding_only(params, idx))))
        sess = ServingSession(
            model, params,
            batcher=BatcherConfig(max_batch=batch, max_wait_s=0.0),
            sla_ms=1e6)
        for b in range(4):
            dense = rng.standard_normal(
                (batch, model.cfg.dense_features)).astype(np.float32)
            sess.submit_batch(dense, mk(pats, b + 10), qid0=b * batch)
            if b >= 1:
                sess.poll()
        sess.drain()
        sess.close()
        pct = sess.percentiles()
        emit(f"sharded_pool/parity_{backend}", "",
             f"bit_exact={exact} served={pct['served']} "
             f"p99_ms={pct['p99_ms']:.2f}")

    # -- host tier: one shm copy of the cold rows, any worker count -------
    # every solo table group below is an ascending contiguous run, so each
    # worker's ColdStore is a zero-copy view into the ONE shared segment;
    # replicating tables 0 and 7 onto every worker adds mapped views but
    # no resident bytes
    host_plcs = {
        1: ShardPlacement.contiguous(t_count, 1),
        2: ShardPlacement(num_tables=t_count, num_shards=2,
                          replicas=((0, 1), (0,), (0,), (0,),
                                    (1,), (1,), (1,), (0, 1)),
                          loads=(1.0,) * t_count, strategy="replicated"),
        4: ShardPlacement(num_tables=t_count, num_shards=4,
                          replicas=((0, 1, 2, 3), (0,), (0,), (1,),
                                    (2,), (3,), (3,), (0, 1, 2, 3)),
                          loads=(1.0,) * t_count, strategy="replicated"),
    }
    for workers, plc in host_plcs.items():
        model = mk_model("pool")
        store = model.ebc.storage
        store.build(params, ps_cfg(), trace=trace, placement=plc,
                    num_workers=workers, num_shards=plc.num_shards)
        idx = jnp.asarray(mk(pats, 8))
        exact = bool(np.array_equal(
            np.asarray(model.embedding_only(params, idx)),
            np.asarray(ref_model.embedding_only(params, idx))))
        acct = store.stats()["pool"]
        store.close()
        emit(f"sharded_pool/host_tier/workers{workers}", "",
             f"bit_exact={exact} "
             f"resident_cold_bytes={acct['resident_cold_bytes']} "
             f"host_view_bytes={acct['host_view_bytes']} "
             f"shared_host_bytes={acct['shared_host_bytes']}")

    # -- shift replay: migration follows the moving hot set ---------------
    for backend in ("sharded", "pool"):
        model = mk_model(backend)
        store = model.ebc.storage
        build_kw = {"num_workers": 2} if backend == "pool" else {}
        store.build(params, ps_cfg(), trace=trace, num_shards=2,
                    placement="contiguous", migration_threshold=1.1,
                    **build_kw)

        def check(p, seed):
            idx = jnp.asarray(mk(p, seed))
            return bool(np.array_equal(
                np.asarray(model.embedding_only(params, idx)),
                np.asarray(ref_model.embedding_only(params, idx))))

        # phase A: the heavy tables sit at the high end of the range
        exact = all(check(pats, s) for s in range(4))    # fills the window
        plan_a = store.plan_migration()
        exact &= check(pats, 4)                          # plan pending
        res_a = (store.install_migration(plan_a) if plan_a
                 else {"migrated": False})
        # phase B: the hot set coalesces onto shard 0's table group (the
        # adversarial drift for whatever placement A installed); 8 batches
        # turn the live window over entirely to the new mix
        shard0 = set(store.placement.shard_tables[0])
        pats_b = mk_pats(tuple("random" if t in shard0 else "one_item"
                               for t in range(t_count)))
        exact &= all(check(pats_b, s) for s in range(5, 13))
        plan_b = store.plan_migration()
        res_b = (store.install_migration(plan_b) if plan_b
                 else {"migrated": False})
        exact &= all(check(pats_b, s) for s in range(13, 16))
        store.close()
        emit(f"sharded_pool/shift_{backend}", "",
             f"bit_exact={exact} "
             f"migrated_a={res_a.get('migrated', False)} "
             f"imb_a_before={res_a.get('imbalance_before', 0.0):.4f} "
             f"imb_a_after={res_a.get('imbalance_after', 0.0):.4f} "
             f"migrated_b={res_b.get('migrated', False)} "
             f"imb_b_before={res_b.get('imbalance_before', 0.0):.4f} "
             f"imb_b_after={res_b.get('imbalance_after', 0.0):.4f}")


def embedding_stage():
    """Fused warm-cache lookup (hit-gather + pooled reduce + miss-list in
    one launch) vs the per-row tier path, per residency leg.

    Both paths serve the SAME parameter-server tiers over a device-resident
    warm payload; `fused` routes through `ParameterServer.lookup_fused`
    (the `PSConfig.fused_lookup` flag), `unfused` through the legacy
    lookup-then-pool pipeline that materializes the dense [B, T, L, D]
    block host-side. Three legs sweep residency: `warm_hit` (traffic
    universe resident after warmup — the leg the fusion exists for),
    `mixed`, and `cold` (the host cold path dominates both). Records
    µs/row (`row_us`), bit-exactness of fused vs unfused output, and the
    achieved cache hit rate. `tools/check_bench.py` enforces within-run
    that fused is no slower than unfused on the warm-hit leg, plus a
    roofline record asserting the fused stage lowers memory-dominant
    (the paper's premise for the embedding stage).
    """
    from repro.core.embedding import _pool_rows_core
    from repro.kernels.embedding_bag import fused_warm_lookup_xla
    from repro.ps import ParameterServer, PSConfig
    from repro.roofline.analyze import roofline_terms
    rows, dim, batch, pool, t_count = 8192, 256, 256, 32, 4
    n_rows = batch * t_count * pool
    rng = np.random.default_rng(SEED)
    tables = rng.normal(size=(t_count, rows, dim)).astype(np.float32)

    # roofline: arithmetic intensity of the fused stage's lowered HLO —
    # a gather + pooled reduce must land memory-dominant
    cache = jnp.asarray(tables[0][:1024])
    slots = jnp.asarray(np.random.default_rng(seeded(1))
                        .integers(0, 1024, (batch, pool)))
    lowered = jax.jit(
        lambda c, s, r: fused_warm_lookup_xla(c, s, r)).lower(
            cache, slots, slots)
    terms = roofline_terms(lowered.compile().as_text(), num_chips=1)
    ai = terms["per_device_flops"] / max(terms["per_device_bytes"], 1.0)
    emit("embedding_stage/roofline", "",
         f"dominant={terms['dominant']} arith_intensity={ai:.6f}")

    def mk(universe, seed):
        return np.random.default_rng(seeded(seed)).integers(
            0, universe, (batch, t_count, pool))

    for leg, warm, universe in (("warm_hit", 1024, 512),
                                ("mixed", 256, 2048),
                                ("cold", 32, rows)):
        ps_f = ParameterServer(
            tables, PSConfig(warm_slots=warm, warm_backing="device",
                             fused_lookup=True, prefetch_depth=0))
        ps_u = ParameterServer(
            tables, PSConfig(warm_slots=warm, warm_backing="device",
                             prefetch_depth=0))
        for s in range(3):                               # warm the tiers
            idx = mk(universe, s)
            ps_f.lookup_fused(idx)
            ps_u.lookup(idx)
        idx = mk(universe, 10)

        def unfused():
            blk = ps_u.lookup(idx)                       # [B, T, L, D]
            pooled = _pool_rows_core(
                jnp.swapaxes(jnp.asarray(blk), 0, 1), None, "sum", pool)
            return jnp.swapaxes(pooled, 0, 1)

        exact = bool(np.array_equal(np.asarray(ps_f.lookup_fused(idx)),
                                    np.asarray(unfused())))
        t_f = timeit_median(lambda: ps_f.lookup_fused(idx), iters=5,
                            warmup=2)
        t_u = timeit_median(unfused, iters=5, warmup=2)
        hit = ps_f.stats()["cache_hit_rate"]
        ps_f.close()
        ps_u.close()
        emit(f"embedding_stage/{leg}/fused", round(t_f * 1e6, 1),
             f"row_us={t_f * 1e6 / n_rows:.4f} bit_exact={exact} "
             f"hit={hit:.3f}")
        emit(f"embedding_stage/{leg}/unfused", round(t_u * 1e6, 1),
             f"row_us={t_u * 1e6 / n_rows:.4f}")


def slo_overload():
    """SLO-driven overload serving: flash-crowd replay on a virtual clock.

    Calibrates the real batch service time on this host, then offers a
    deterministic flash-crowd trace (base 0.5x the service rate, a 4x
    spike) through `ServingSession(slo=..., clock=VirtualClock())` with
    the SLO controller off vs on, plus an SLO-on steady leg. Because the
    offered load is expressed in multiples of the MEASURED service rate
    and arrivals live on the virtual clock, the comparison is
    host-independent: `tools/check_bench.py` enforces (within one run)
    that SLO-on recovers its windowed p99 to the target after the spike
    while SLO-off does not, that the spike's shed fraction stays bounded,
    and that the steady leg sheds nothing.

    A second leg pair (`bigbatch_off/on`) exercises the ladder's
    batch-shrink rung on the failure mode it exists for: a latency-bound
    misconfiguration (oversized batching window, load deep in capacity)
    where shedding would be the wrong fix — the armed controller must
    shrink the batch quantum until the windowed p99 fits the target,
    while the unarmed leg keeps breaching.
    """
    from repro.ps import PSConfig
    from repro.serving import BatcherConfig, ServingSession, SLOConfig
    from repro.traffic import VirtualClock, make_traffic, replay
    rows, dim, batch, pool, t_count = 2000, 16, 32, 10, 4

    def mk_session(slo, batcher=None):
        cfg = DLRMConfig(embedding=EmbeddingStageConfig(
            num_tables=t_count, rows=rows, dim=dim, pooling=pool,
            backend="xla", storage="tiered"),
            bottom_mlp=(32, dim), top_mlp=(16, 1))
        model = DLRM(cfg)
        params = model.init(jax.random.PRNGKey(SEED))
        gen0 = make_traffic("steady", base_qps=100.0, num_tables=t_count,
                            rows=rows, pooling=pool, seed=seeded(0))
        trace = np.stack([q.indices for q in gen0.queries(64)])
        model.ebc.storage.build(
            params,
            PSConfig(hot_rows=rows // 10, warm_slots=rows // 10,
                     prefetch_depth=2, window_batches=8,
                     async_prefetch=True),
            trace=trace)
        return ServingSession(
            model, params,
            batcher=batcher or BatcherConfig(max_batch=batch,
                                             max_wait_s=0.002),
            slo=slo, clock=VirtualClock())

    # calibrate: real batch service time -> offered load in service-rate
    # multiples (host-independent overload factors)
    sess = mk_session(None)
    dense = np.zeros((batch, 13), np.float32)
    idx = np.zeros((batch, t_count, pool), np.int32)
    t0 = time.perf_counter()
    for _ in range(5):
        np.asarray(sess._forward(dense, idx))
    t_b = (time.perf_counter() - t0) / 5
    sess.close()
    svc_qps = batch / t_b
    target_ms = 6.0 * t_b * 1e3
    base_qps, spike_qps = 0.5 * svc_qps, 4.0 * svc_qps
    # the steady leg runs at a deeper margin (0.25x): it asserts that an
    # ARMED controller sheds nothing in steady state, and t_b is calibrated
    # once up front — per-batch service drifting a few percent over the
    # flash legs must not turn headroom into backlog
    steady_qps = 0.25 * svc_qps
    spike_start, spike_len, post = 8.0 * t_b, 24.0 * t_b, 16.0 * t_b
    n_flash = int(base_qps * (spike_start + post) + spike_qps * spike_len)
    n_steady = int(steady_qps * (spike_start + spike_len + post))

    def leg(kind, slo_on, n, qps):
        slo = (SLOConfig(target_p99_ms=target_ms, shed_deadline_frac=0.4,
                         window_queries=256)
               if slo_on else None)
        sess = mk_session(slo)
        gen = make_traffic(kind, base_qps=qps, spike_qps=spike_qps,
                           spike_start_s=spike_start, spike_len_s=spike_len,
                           num_tables=t_count, rows=rows, pooling=pool,
                           seed=seeded(1))
        rep = replay(sess, gen.queries(n), window_queries=256)
        pct = rep.percentiles
        sess.close()
        return rep, pct

    for name, kind, on, n, qps in (
            ("flash_off", "flash", False, n_flash, base_qps),
            ("flash_on", "flash", True, n_flash, base_qps),
            ("steady_on", "steady", True, n_steady, steady_qps)):
        rep, pct = leg(kind, on, n, qps)
        post_p99 = rep.final_windowed_p99_ms() or 0.0
        line = (f"post_p99_ms={post_p99:.2f} target_ms={target_ms:.2f} "
                f"shed_frac={rep.shed_frac:.3f} answered={rep.served}")
        if on:
            line += (f" breaches={pct.get('slo_breaches', 0)} "
                     f"degraded_batches={pct.get('slo_degraded_batches', 0)} "
                     f"shrinks={pct.get('slo_batch_shrinks', 0)}")
        emit(f"slo_overload/{name}", "", line)

    # batch-shrink rung: a LATENCY-bound misconfiguration (the batching
    # window itself blows the target — offered load is deep in capacity,
    # so shedding/degrading would be the wrong fix). The shrink rung
    # halves max_batch (scaling the window) until the formation wait fits
    # under the target; shedding is disarmed (shed_deadline_frac=0) so
    # the rung is the only mechanism in play, and recover_frac is set low
    # enough that the controller holds the shrunken quantum instead of
    # regrowing back into the breach.
    big_wait_s = 8.0 * t_b
    big_target_ms = 5.0 * t_b * 1e3
    big_qps = 0.125 * svc_qps          # fill time for a full batch ~ window
    n_big = 24 * batch
    for name, slo in (
            ("bigbatch_off", None),
            ("bigbatch_on", SLOConfig(
                target_p99_ms=big_target_ms, window_queries=64,
                check_every_batches=2, recover_frac=0.2, degrade=False,
                shed_deadline_frac=0.0, min_batch=batch // 4))):
        sess = mk_session(slo, batcher=BatcherConfig(max_batch=batch,
                                                     max_wait_s=big_wait_s))
        gen = make_traffic("steady", base_qps=big_qps, num_tables=t_count,
                           rows=rows, pooling=pool, seed=seeded(2))
        rep = replay(sess, gen.queries(n_big), window_queries=64)
        pct = rep.percentiles
        sess.close()
        post_p99 = rep.final_windowed_p99_ms() or 0.0
        line = (f"post_p99_ms={post_p99:.2f} target_ms={big_target_ms:.2f} "
                f"shed_frac={rep.shed_frac:.3f} answered={rep.served}")
        if slo is not None:
            line += (f" breaches={pct.get('slo_breaches', 0)} "
                     f"degraded_batches={pct.get('slo_degraded_batches', 0)} "
                     f"shrinks={pct.get('slo_batch_shrinks', 0)}")
        emit(f"slo_overload/{name}", "", line)


def multi_tenant():
    """Multi-tenant serving: two tenants over ONE shared sharded backend.

    A steady tenant and a flash-crowd neighbor replay through one
    `TenantManager` on a virtual clock, twice: fair scheduling with the
    fair-share arbiter ON vs fifo scheduling with it OFF. All time
    quantities are multiples of the MEASURED shared batch service time
    `t_b` (and the query counts are fixed multiples of the batch size),
    so the legs are host-independent. `tools/check_bench.py` enforces,
    within the fresh run: containment (with the arbiter the flash crowd
    may not push the steady tenant's p99 past the SLO bound; without it,
    it must — else the comparison is vacuous), per-tenant bit-exactness
    vs a fresh device-storage reference, and arbiter budget conservation
    (every round's split sums to <= the one shared budget).
    """
    from repro.ps import PSConfig
    from repro.serving import (ArbiterConfig, BatcherConfig, TenantManager,
                               TenantSpec, configure)
    from repro.traffic import VirtualClock, make_traffic, replay_tenants
    rows, dim, batch, t_count = 1000, 16, 16, 3
    poolings = {"steady": 4, "flash": 4}

    def specs():
        out = []
        for i, name in enumerate(("steady", "flash")):
            cfg = DLRMConfig(embedding=EmbeddingStageConfig(
                num_tables=t_count, rows=rows, dim=dim,
                pooling=poolings[name], backend="xla", storage="device"),
                bottom_mlp=(32, dim), top_mlp=(16, 1))
            model = DLRM(cfg)
            out.append((TenantSpec(
                name=name, model=model,
                params=model.init(jax.random.PRNGKey(seeded(i)))), cfg))
        return out

    def mk_manager(scheduling, arbiter, max_wait_s):
        built = specs()
        mgr = TenantManager(
            [s for s, _ in built], backend="sharded",
            batcher=BatcherConfig(max_batch=batch, max_wait_s=max_wait_s),
            controllers=configure(
                arbiter=(ArbiterConfig(every_batches=8,
                                       budget_fallback_bytes=32 << 20)
                         if arbiter else None)),
            scheduling=scheduling, clock=VirtualClock(),
            num_shards=2,
            ps_cfg=PSConfig(hot_rows=rows // 10, warm_slots=rows // 10,
                            prefetch_depth=2, window_batches=8))
        return mgr, built

    # calibrate the shared batch service time once (probe, not traffic)
    mgr, _ = mk_manager("fair", False, 0.002)
    sess = mgr.session("steady")
    dense = np.zeros((batch, 13), np.float32)
    idx = np.zeros((batch, t_count, poolings["steady"]), np.int32)
    t0 = time.perf_counter()
    for _ in range(5):
        np.asarray(sess._forward(dense, idx))
    t_b = (time.perf_counter() - t0) / 5
    mgr.close()
    svc_qps = batch / t_b
    # the containment bound sits at the log-midpoint of the two regimes:
    # fair+arbiter keeps the steady tenant's p99 around ~10 t_b (batching
    # window + a few interleaved service quanta), fifo queues it behind
    # the whole flash backlog (~100 t_b) — 30 t_b separates them with
    # comfortable margin on both sides on any host
    target_ms = 30.0 * t_b * 1e3
    base_qps = 0.25 * svc_qps                 # per tenant: 0.5x combined
    spike_start, spike_len, post = 8.0 * t_b, 12.0 * t_b, 16.0 * t_b
    n_steady = int(base_qps * (spike_start + spike_len + post))
    n_flash = int(base_qps * (spike_start + post)
                  + 4.0 * svc_qps * spike_len)

    def leg(name, scheduling, arbiter):
        mgr, built = mk_manager(scheduling, arbiter, max_wait_s=2.0 * t_b)
        try:
            streams = {
                "steady": make_traffic(
                    "steady", base_qps=base_qps, num_tables=t_count,
                    rows=rows, pooling=poolings["steady"],
                    seed=seeded(2)).queries(n_steady),
                "flash": make_traffic(
                    "flash", base_qps=base_qps, spike_qps=4.0 * svc_qps,
                    spike_start_s=spike_start, spike_len_s=spike_len,
                    num_tables=t_count, rows=rows,
                    pooling=poolings["flash"],
                    seed=seeded(3)).queries(n_flash),
            }
            reports = replay_tenants(mgr, streams, window_queries=64)
            pct = mgr.percentiles()
            rng = np.random.default_rng(seeded(4))
            for (spec, cfg), rep_name in zip(built, ("steady", "flash")):
                rep, tp = reports[rep_name], pct["tenants"][rep_name]
                # bit-exactness probe: tenant forward vs a fresh
                # device-storage model on the same params
                d = rng.normal(size=(8, cfg.dense_features)).astype(
                    np.float32)
                i = rng.integers(0, rows, size=(
                    8, t_count, poolings[rep_name])).astype(np.int32)
                got = np.asarray(spec.model.forward(spec.params, d, i))
                ref = np.asarray(DLRM(cfg).forward(
                    jax.tree_util.tree_map(np.asarray, spec.params), d, i))
                emit(f"multi_tenant/{name}/{rep_name}", "",
                     f"p99_ms={tp['p99_ms']:.2f} target_ms={target_ms:.2f} "
                     f"answered={rep.served} shed_frac={rep.shed_frac:.3f} "
                     f"bit_exact={np.array_equal(got, ref)}")
            st = mgr.stats()
            line = (f"num_tenants={st['shared']['num_tenants']} "
                    f"device_bytes={st['shared']['device_bytes']}")
            if mgr.arbiter is not None:
                conserved = all(
                    sum(ev["budgets"].values()) <= ev["budget_bytes"]
                    for ev in mgr.arbiter.events)
                line += (f" arbiter_rounds={len(mgr.arbiter.events)} "
                         f"conserved={conserved}")
            emit(f"multi_tenant/{name}/shared", "", line)
        finally:
            mgr.close()

    leg("fair_arbiter", "fair", True)
    leg("fifo_static", "fifo", False)


def online_update():
    """Zero-downtime online model updates: guarded mid-stream delta refresh.

    Serves the SAME deterministic trace through a tiered `ServingSession`
    twice: a `silent` leg with the update machinery armed but idle (the
    trainer never publishes past the base snapshot) and an `updates` leg
    where two row deltas and one delta big enough to trip the
    full-snapshot fallback land mid-stream. Every answered batch in both
    legs is replayed through a dense device clone holding the snapshot of
    the batch's PINNED version, using the session's own engine shapes —
    `bit_exact` is the epoch-guard contract (a query admitted at version
    v is answered by exactly v's weights, even while later versions
    install). `tools/check_bench.py` enforces, within the fresh run: both
    legs bit-exact, the updates leg applied 2 deltas + 1 full with zero
    rollbacks and zero sheds, and its p99 stays within a bound of the
    silent leg's — version swaps must not wreck the serving tail.
    """
    import tempfile
    from repro.checkpoint import ModelUpdateStream
    from repro.ps import PSConfig
    from repro import serving
    from repro.serving import QueryShedError

    rows, dim, t_count, pool, batch, steps = 512, 16, 4, 4, 16, 24

    def leg(name, publish_steps):
        cfg = DLRMConfig(embedding=EmbeddingStageConfig(
            num_tables=t_count, rows=rows, dim=dim, pooling=pool,
            backend="xla", storage="tiered"),
            bottom_mlp=(32, dim), top_mlp=(16, 1))
        model = DLRM(cfg)
        params = model.init(jax.random.PRNGKey(SEED))
        tables0 = np.asarray(params["embedding"]["tables"])[:t_count].copy()
        model.ebc.storage.build(
            params, PSConfig(hot_rows=rows // 8, warm_slots=rows // 8,
                             prefetch_depth=2))
        # dense clone for the per-version oracle replay
        omodel = DLRM(DLRMConfig(embedding=EmbeddingStageConfig(
            num_tables=t_count, rows=rows, dim=dim, pooling=pool,
            backend="xla", storage="device"),
            bottom_mlp=(32, dim), top_mlp=(16, 1)))
        rng_t = np.random.default_rng(seeded(11))   # traffic: shared by legs
        rng_u = np.random.default_rng(seeded(12))   # update payloads only
        with tempfile.TemporaryDirectory() as d:
            pub = ModelUpdateStream(d)
            pub.publish_full(tables0)        # v1 base; consumers join here
            sess = serving.ServingSession(
                model, params,
                batcher=serving.BatcherConfig(max_batch=batch,
                                              max_wait_s=0.0),
                controllers=serving.configure(
                    updates=serving.UpdateConfig(
                        stream=ModelUpdateStream(d))))
            batches, traffic, sheds = [], [], 0
            sess.server.on_batch = lambda b, s: batches.append(
                ([q.qid for q in b], s.copy()))
            snapshots = {0: tables0.copy(), 1: tables0.copy()}
            cur = tables0.copy()
            for step in range(steps):
                dense = rng_t.normal(size=(batch, 13)).astype(np.float32)
                idx = rng_t.integers(0, rows, size=(batch, t_count, pool)
                                     ).astype(np.int32)
                traffic.extend((dense[i], idx[i]) for i in range(batch))
                try:
                    sess.submit_batch(dense, idx)
                except QueryShedError:
                    sheds += 1
                while sess.poll(force=True):
                    pass
                if step in publish_steps:
                    if publish_steps[step] == "delta":
                        t = step % t_count
                        r = rng_u.choice(rows, size=8, replace=False)
                        v = rng_u.normal(size=(8, dim)).astype(np.float32)
                        cur[t, r] = v
                        ver = pub.publish_delta({t: (r, v)})
                    else:   # touch >half of all rows -> full fallback
                        r = np.arange(rows)
                        changed = {}
                        for t in range(t_count - 1):
                            v = rng_u.normal(size=(rows, dim)
                                             ).astype(np.float32)
                            cur[t] = v
                            changed[t] = (r, v)
                        ver = pub.publish_delta(changed)
                    snapshots[ver] = cur.copy()
            sess.drain()
            pct = sess.percentiles()
            mismatched = 0
            rest = {}        # per-version jit, matching the engine shapes
            for qids, scores in batches:
                pins = {sess.version_of(q) for q in qids}
                if len(pins) != 1:
                    mismatched += 1          # epoch guard broke batching
                    continue
                v = pins.pop()
                op = dict(params)
                op["embedding"] = dict(params["embedding"])
                op["embedding"]["tables"] = jnp.asarray(snapshots[v])
                if v not in rest:
                    rest[v] = jax.jit(
                        lambda dn, po, p=op: omodel.forward_from_pooled(
                            p, dn, po))
                dense = np.zeros((batch, 13), np.float32)
                idx = np.zeros((batch, t_count, pool), np.int32)
                for i, q in enumerate(qids):
                    dense[i], idx[i] = traffic[q]
                pooled = omodel.ebc.apply(op["embedding"], idx)
                ref = np.asarray(rest[v](jnp.asarray(dense),
                                         pooled))[:len(qids)]
                if not np.array_equal(scores, ref):
                    mismatched += 1
            served = sum(len(q) for q, _ in batches)
            sess.close()
            emit(f"online_update/{name}", "",
                 f"p99_ms={pct['p99_ms']:.2f} served={served} "
                 f"sheds={sheds} bit_exact={mismatched == 0} "
                 f"model_version={pct['model_version']} "
                 f"updates_applied={pct['updates_applied']} "
                 f"updates_delta={pct['updates_delta']} "
                 f"updates_full={pct['updates_full']} "
                 f"rolled_back={pct['updates_rolled_back']} "
                 f"update_stall_ms={pct['update_stall_s'] * 1e3:.2f}")

    leg("silent", {})
    leg("updates", {6: "delta", 12: "delta", 18: "full"})


ALL = [tab3_unique_access, fig5_coverage, fig1_embedding_contribution,
       fig6_pipeline_sweep, fig9_prefetch_distance, fig11_l2p_pooling,
       fig12_embedding_speedup, fig12_measured_cpu, fig13_e2e_speedup,
       fig14_gap, fig15_buffer_schemes, fig16_no_optmt, fig17_heterogeneous,
       tab45_microarch, tiered_ps_capacity_sweep, tiered_ps_sync_vs_async,
       tiered_ps_autotune, storage_backends, sharded_balance,
       sharded_migration, sharded_pool, embedding_stage, slo_overload,
       multi_tenant, online_update]


def main(argv: list[str] | None = None) -> None:
    global _CURRENT_SWEEP, SEED
    from repro import storage as storage_registry
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sweep", action="append", default=None,
                    choices=[fn.__name__ for fn in ALL],
                    help="run only this sweep (repeatable; default: all)")
    ap.add_argument("--backend", action="append", default=None,
                    choices=storage_registry.available(),
                    help="storage backend(s) for the storage_backends "
                         "sweep, resolved through the repro.storage "
                         "registry (repeatable; default: all registered)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write structured records (schema_version 1: "
                         "sweep/name/metric/value/units per record) for "
                         "tools/check_bench.py")
    ap.add_argument("--seed", type=int, default=0,
                    help="global seed offset threaded through every "
                         "sweep's patterns/rngs/keys (default 0 "
                         "reproduces the checked-in baseline exactly); "
                         "recorded at the top level of --json output")
    args = ap.parse_args(argv)
    enable_compile_cache()
    SEED = args.seed
    selected = (ALL if args.sweep is None
                else [fn for fn in ALL if fn.__name__ in args.sweep])
    print("name,us_per_call,derived")
    for fn in selected:
        _CURRENT_SWEEP = fn.__name__
        if fn is storage_backends:
            fn(args.backend)
        else:
            fn()
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"schema_version": 1, "seed": SEED,
                       "records": JSON_RECORDS}, f, indent=1)
        print(f"wrote {len(JSON_RECORDS)} records to {args.json}",
              file=sys.stderr)


if __name__ == "__main__":
    main()
