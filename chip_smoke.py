#!/usr/bin/env python3
"""Serve the DLRM main path on one TPU chip and check what comes out.

    python chip_smoke.py [--seed N]

One process, through the entry points a user calls (`ServingSession` over
the storage protocol), at the widths of `configs/dlrm_production.py`
(dim 128, pooling 150, batch 2048, 500,000 rows per table, float32,
bottom MLP 1024-512-128-128, top MLP 128-64-1) with random weights and
med_hot traffic made from `--seed`:

  A  `device` backend, backend="auto" (the Pallas bag kernel on a TPU),
     24 of the paper's 250 tables. The compiled engine must contain the
     kernel (`tpu_custom_call`); its logits and pooled rows must agree
     with the same model on backend="xla". Run again with the tables
     stored hot-first (`pinned_rows`).
  B  `tiered` backend with the fused warm-cache kernel, 8 tables on the
     host cold tier; pooled rows and logits against the dense float32
     reference (`embedding_bag_ref`).
  C  `pool` backend: 2 worker processes (JAX on the host CPU) with a
     host-backed warm cache, 4 tables, served while this process holds
     the chip; every worker must answer its heartbeat.

Each phase prints one line, with its set-up time (session construction:
compile plus one warm-up batch) cold and again with the persistent
compile cache warm, each with the cache hits and misses it saw. The cache lives where JAX_COMPILATION_CACHE_DIR says,
else in `<checkout>/.jax_cache`. The last line is one JSON object,
`{"ok": true, "device": {...}}`. With no TPU, or when any phase fails,
the script exits non-zero and prints no such line.
"""
import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

BATCH = 2048
SERVED_BATCHES = 4
HOTNESS = "med_hot"
TABLES_A, TABLES_B, TABLES_C = 24, 8, 4
PINNED_ROWS = 4096            # per table, in the kernel's VMEM hot block
HOT_ROWS, WARM_SLOTS = 4096, 65536
REF_CHUNK = 256               # reference rows per XLA gather ([T,256,L,D])

# Tolerances, both against a float32 reference on the same chip.
# Pooled rows: a bag sums 150 float32 rows of |x| ~ 0.09, so |pooled| ~ 1;
# two summation orders differ by a few ULP (~1e-7 each). 1e-4 leaves
# ~1000 ULP of headroom, while one wrong or missing row moves a pooled
# element by ~0.09 — three orders of magnitude above it.
POOLED_ATOL = 1e-4
# Logits: the interaction and MLPs run at the TPU's default float32
# matmul precision, which rounds operands to bfloat16 (8-bit mantissa).
# A 1-ULP difference in a pooled element can flip one operand's bf16
# rounding (2^-8 relative), and such flips add up through 300 pairwise
# dot products and two MLP layers. 5e-2 absolute bounds that; a wrong
# embedding stage moves logits by O(1).
LOGIT_ATOL = 5e-2


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CacheEvents:
    """Counts persistent compile-cache hits and misses (JAX monitoring)."""

    def __init__(self):
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def model_config(num_tables: int, **embedding):
    """The paper's model with its table count cut to `num_tables`."""
    from repro.configs.dlrm_production import CONFIG
    emb = dataclasses.replace(CONFIG.embedding, num_tables=num_tables,
                              shard_pad_tables=0, **embedding)
    return dataclasses.replace(CONFIG, embedding=emb)


def query_stream(cfg, seed: int):
    from repro.data import DLRMQueryStream
    emb = cfg.embedding
    return DLRMQueryStream(num_tables=emb.num_tables, rows=emb.rows,
                           pooling=emb.pooling, batch_size=BATCH,
                           hotness=HOTNESS, seed=seed)


def open_session(model, params):
    from repro.serving import BatcherConfig, ServingSession
    return ServingSession(model, params,
                          batcher=BatcherConfig(max_batch=BATCH,
                                                max_wait_s=0.0),
                          sla_ms=60_000)


def serve(sess, batches) -> np.ndarray:
    """Serve `batches` through a fresh session; scores in submission
    order (query ids 0..n-1)."""
    out = {}
    sess.server.on_batch = lambda qs, scores: out.update(
        zip((q.qid for q in qs), np.asarray(scores)))
    for k, b in enumerate(batches):
        sess.submit_batch(b.dense, b.indices, qid0=k * BATCH)
    sess.drain(timeout_s=600.0)
    n = len(batches) * BATCH
    check(sess.stats.served == n and len(out) == n,
          f"served {sess.stats.served} of {n} queries")
    return np.array([out[i] for i in range(n)], np.float32)


def timed_session(model, params, cache: CacheEvents, label: str):
    """Open a session; returns it and its set-up time with the persistent
    cache's hits and misses meanwhile, as `label`-named fields."""
    h0, m0 = cache.hits, cache.misses
    t0 = time.perf_counter()
    sess = open_session(model, params)
    return sess, (f"setup_{label}_s={time.perf_counter() - t0:.3f} "
                  f"{label}_cache_hits={cache.hits - h0} "
                  f"{label}_cache_misses={cache.misses - m0}")


def warm_setup(model, params, cache: CacheEvents) -> str:
    """Set-up again after dropping the in-memory compile caches: every
    program now comes from the persistent cache."""
    jax.clear_caches()
    sess, warm = timed_session(model, params, cache, "warm")
    sess.server.close()          # the storage stays with the first session
    return warm


def reference_pooled(tables, indices):
    """Dense float32 reference [B, T, D] of raw `indices` [B, T, L] over
    device `tables` [T, R, D], REF_CHUNK rows at a time."""
    from repro.kernels.embedding_bag import embedding_bag_ref
    chunks = []
    for s in range(0, len(indices), REF_CHUNK):
        idx = jnp.asarray(indices[s:s + REF_CHUNK])
        chunks.append(jnp.stack(
            [embedding_bag_ref(tables[t], idx[:, t])
             for t in range(tables.shape[0])], axis=1))
    return np.asarray(jnp.concatenate(chunks))


def max_diff(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def phase_a(seed: int, cache: CacheEvents, pinned: int) -> str:
    """`device` backend on the Pallas kernel vs the same model on XLA."""
    from repro.core import plan_from_trace
    from repro.models.dlrm import DLRM
    cfg = model_config(TABLES_A, storage="device", backend="auto",
                       pinned_rows=pinned)
    emb = cfg.embedding
    stream = query_stream(cfg, seed)
    plans = None
    if pinned:
        trace = stream.sample_trace(2)
        plans = [plan_from_trace(trace[:, t], emb.rows, pinned)
                 for t in range(emb.num_tables)]
    model = DLRM(cfg, plans)
    params = jax.block_until_ready(model.init(jax.random.PRNGKey(seed)))
    batches = [stream.next_batch() for _ in range(SERVED_BATCHES)]
    sess, cold = timed_session(model, params, cache, "cold")
    with sess:
        b0 = batches[0]
        hlo = sess.engine_jit.lower(params, b0.dense,
                                    b0.indices).compile().as_text()
        check("tpu_custom_call" in hlo,
              "the device engine was compiled without the Pallas kernel")
        scores = serve(sess, batches)
        served = sess.stats.served
        host_batch_s = float(np.mean(sess.stats.batch_latencies_s))
        warm = warm_setup(model, params, cache)
    check(np.all(np.isfinite(scores)), "non-finite served logits")
    # the same model and weights on the XLA path
    model_x = DLRM(dataclasses.replace(
        cfg, embedding=dataclasses.replace(emb, backend="xla")), plans)
    fwd_x = jax.jit(model_x.forward)
    ref_logits = np.concatenate([
        np.asarray(fwd_x(params, b0.dense[s:s + REF_CHUNK],
                         b0.indices[s:s + REF_CHUNK]))
        for s in range(0, BATCH, REF_CHUNK)])
    pooled = np.asarray(jax.jit(model.embedding_only)(params, b0.indices))
    emb_x = jax.jit(model_x.embedding_only)
    ref_pooled = np.concatenate([
        np.asarray(emb_x(params, b0.indices[s:s + REF_CHUNK]))
        for s in range(0, BATCH, REF_CHUNK)])
    d_pooled = max_diff(pooled, ref_pooled)
    d_logits = max_diff(scores[:BATCH], ref_logits)
    check(d_pooled <= POOLED_ATOL,
          f"phase A pooled rows differ from XLA by {d_pooled}")
    check(d_logits <= LOGIT_ATOL,
          f"phase A logits differ from XLA by {d_logits}")
    return (f"phase=A storage=device backend=auto kernel=pallas "
            f"pinned_rows={pinned} tables={emb.num_tables} "
            f"batches_served={served // BATCH} queries_served={served} "
            f"tpu_custom_call=True max_abs_diff_pooled_vs_xla={d_pooled!r} "
            f"max_abs_diff_logits_vs_xla={d_logits!r} "
            f"{cold} {warm} "
            f"host_wall_s_per_batch={host_batch_s:.3f}")


def host_backed_phase(seed: int, cache: CacheEvents, *,
                      name: str, storage: str, num_tables: int, ps_cfg,
                      **build) -> tuple:
    """Build a host-backed backend, serve through a session, and compare
    with the dense float32 reference. Returns (line, storage status)."""
    from repro.models.dlrm import DLRM
    cfg = model_config(num_tables, storage=storage)
    stream = query_stream(cfg, seed)
    model = DLRM(cfg)
    params = jax.block_until_ready(model.init(jax.random.PRNGKey(seed)))
    tables = params["embedding"]["tables"]
    trace = stream.sample_trace(2)
    t0 = time.perf_counter()
    model.ebc.storage.build(params, ps_cfg, trace=trace, **build)
    build_s = time.perf_counter() - t0
    caps = model.ebc.storage.capabilities()
    batches = [stream.next_batch() for _ in range(SERVED_BATCHES)]
    extra = stream.next_batch()
    sess, cold = timed_session(model, params, cache, "cold")
    try:
        scores = serve(sess, batches)
        served = sess.stats.served
        host_batch_s = float(np.mean(sess.stats.batch_latencies_s))
        # one more batch straight through the storage protocol: its
        # pooled rows against the reference (caches warm by now)
        pooled = np.asarray(model.ebc.apply(params["embedding"],
                                            extra.indices))
        status = (model.ebc.storage.worker_status()
                  if hasattr(model.ebc.storage, "worker_status") else None)
        warm = warm_setup(model, params, cache)
    finally:
        sess.close()
    check(np.all(np.isfinite(scores)), f"phase {name}: non-finite logits")
    d_pooled = max_diff(pooled, reference_pooled(tables, extra.indices))
    b0 = batches[0]
    ref_logits = np.asarray(jax.jit(model.forward_from_pooled)(
        params, b0.dense,
        jnp.asarray(reference_pooled(tables, b0.indices))))
    d_logits = max_diff(scores[:BATCH], ref_logits)
    check(d_pooled <= POOLED_ATOL,
          f"phase {name} pooled rows differ from the reference by "
          f"{d_pooled}")
    check(d_logits <= LOGIT_ATOL,
          f"phase {name} logits differ from the reference by {d_logits}")
    line = (f"phase={name} storage={storage} tables={num_tables} "
            f"fused_lookup={caps.fused_lookup} "
            f"warm_backing={ps_cfg.warm_backing} "
            f"batches_served={served // BATCH} queries_served={served} "
            f"max_abs_diff_pooled_vs_ref={d_pooled!r} "
            f"max_abs_diff_logits_vs_ref={d_logits!r} "
            f"build_s={build_s:.3f} {cold} {warm} "
            f"host_wall_s_per_batch={host_batch_s:.3f}")
    return line, caps, status


def phase_b(seed: int, cache: CacheEvents) -> str:
    from repro.ps import PSConfig
    line, caps, _ = host_backed_phase(
        seed, cache, name="B", storage="tiered",
        num_tables=TABLES_B,
        ps_cfg=PSConfig(hot_rows=HOT_ROWS, warm_slots=WARM_SLOTS,
                        warm_backing="device", fused_lookup=True))
    check(caps.fused_lookup, "phase B: the tiered backend is not fused")
    return line


def phase_c(seed: int, cache: CacheEvents) -> str:
    from repro.ps import PSConfig
    line, _, status = host_backed_phase(
        seed, cache, name="C", storage="pool",
        num_tables=TABLES_C,
        ps_cfg=PSConfig(hot_rows=HOT_ROWS, warm_slots=WARM_SLOTS,
                        warm_backing="host"),
        num_workers=2)
    alive = [w for w in status if w["alive"]]
    check(len(status) == 2 and len(alive) == 2,
          f"phase C: workers alive {len(alive)}/{len(status)}")
    check(all(w.get("jax_platforms") == "cpu" for w in status),
          "phase C: a pool worker runs JAX off the host CPU")
    return line + f" workers_alive={len(alive)}/{len(status)}"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and traffic")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found — JAX's first device is "
                 f"{dev.platform!r}; this check runs only on a TPU chip")
    from repro.utils import enable_compile_cache
    cache_dir = enable_compile_cache()
    cache = CacheEvents()
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} compile_cache={cache_dir}",
          flush=True)
    print(f"cut: dlrm-production at full width, tables 250 -> "
          f"{TABLES_A} (A), {TABLES_B} (B), {TABLES_C} (C); "
          f"shard_pad_tables 6 -> 0; batch={BATCH} "
          f"batches_per_phase={SERVED_BATCHES}", flush=True)

    for pinned in (0, PINNED_ROWS):
        print(phase_a(args.seed, cache, pinned), flush=True)
        gc.collect()
    print(phase_b(args.seed + 1, cache), flush=True)
    gc.collect()
    print(phase_c(args.seed + 2, cache), flush=True)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
