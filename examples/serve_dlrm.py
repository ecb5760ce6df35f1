"""End-to-end driver: serve a DLRM with batched requests (the paper's kind).

Streams queries across the paper's hotness spectrum through the batching
inference server, reports per-hotness latency percentiles and the embedding
stage share — a scaled-down CPU rendition of paper Figs. 1/13.

The storage backend comes from the `repro.storage` registry: `device`
(tables HBM-resident, the dense baseline), `tiered` (the repro/ps
hot/warm/cold parameter server — beyond-HBM serving), or `sharded`
(table-wise partition of the tiered store across `--shards` workers, one
merged stats report). The `ServingSession` facade owns batcher + engine +
storage and drives prefetch/refresh generically through the protocol, so
the cache/overlap columns appear for any async-capable backend. (The PR-2
shim path — `build_parameter_server` + `InferenceServer(ps=...)` — is
gone; see the docs/serving.md migration table for the replacements.)

`--tenants N` switches to multi-tenant serving: N independent DLRMs
bound to ONE shared sharded/pool backend through a `TenantManager`, each
with its own stats namespace and SLO controller, a fair-share arbiter
re-splitting device budget and prefetch depth from live per-tenant load.
Per-tenant traffic replays through `replay_tenants` on one virtual
clock, so tenants contend for real serving time.

`--update-every N` arms zero-downtime online model updates: a
trainer-side `ModelUpdateStream` publishes a delta touching
`--update-rows FRAC` of each target table's rows every N batches, and
the session installs each version between batches behind the epoch
guard — in-flight queries finish on the version they were admitted
under, and the summary line reports the final model version, how many
deltas/full snapshots landed, and the total update stall.

`--trace` switches to timestamped-trace replay (repro.traffic): queries
arrive on a virtual clock following a named rate profile (steady Zipf,
diurnal sinusoid, flash-crowd spike, hotness shift) at a rate calibrated
to this host's measured service rate, so "overload" means the same thing
everywhere. `--slo-p99-ms` arms the SLO controller on top — admission
control sheds (typed) when the predicted queue wait blows the deadline
budget, and the escalation ladder can drop into degraded warm-cache-only
serving. The run ends with a shed/degraded summary table (see
docs/serving.md "Serving under overload").

    PYTHONPATH=src python examples/serve_dlrm.py [--queries 256]
    PYTHONPATH=src python examples/serve_dlrm.py --storage tiered
    PYTHONPATH=src python examples/serve_dlrm.py --storage sharded --shards 4
    PYTHONPATH=src python examples/serve_dlrm.py --storage pool --workers 2
    PYTHONPATH=src python examples/serve_dlrm.py --storage tiered --async \
        --auto-budget-kib 4096 --warm-backing device
    PYTHONPATH=src python examples/serve_dlrm.py --tenants 2
    PYTHONPATH=src python examples/serve_dlrm.py --storage tiered \
        --trace flash --slo-p99-ms 20
    PYTHONPATH=src python examples/serve_dlrm.py --storage tiered \
        --update-every 4 --update-rows 0.02
"""
import argparse
import tempfile
import time

import jax
import numpy as np

from repro import storage as storage_registry
from repro.core import EmbeddingStageConfig
from repro.data import DLRMQueryStream
from repro.models.dlrm import DLRM, DLRMConfig
from repro.ps import AutoTuneConfig, PSConfig
from repro.serving import BatcherConfig, ServingSession
from repro.utils import enable_compile_cache

HOTNESS = ("one_item", "high_hot", "med_hot", "low_hot", "random")


def parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--tables", type=int, default=8)
    ap.add_argument("--rows", type=int, default=50_000)
    ap.add_argument("--pooling", type=int, default=20)
    ap.add_argument("--storage", choices=storage_registry.available(),
                    default="device",
                    help="storage backend (repro.storage registry)")
    ap.add_argument("--shards", type=int, default=2,
                    help="sharded/pool: table-wise shard workers")
    ap.add_argument("--workers", type=int, default=2,
                    help="pool: worker PROCESSES hosting the shards "
                         "(per-worker device caches over one shared host "
                         "cold tier)")
    ap.add_argument("--placement", choices=("contiguous", "balanced"),
                    default="contiguous",
                    help="sharded: table-to-shard assignment — legacy "
                         "contiguous split or frequency-aware LPT "
                         "balancing from the trace (prints the shard "
                         "load table)")
    ap.add_argument("--hot-rows", type=int, default=2500,
                    help="tiered/sharded: device-pinned rows per table")
    ap.add_argument("--warm-slots", type=int, default=2500,
                    help="tiered/sharded: warm-cache slots per table")
    ap.add_argument("--refresh-every", type=int, default=8,
                    help="re-pin the hot set every N batches")
    ap.add_argument("--async", dest="async_mode", action="store_true",
                    help="threaded prefetch (double buffer) + "
                         "helper-thread hot-set re-planning")
    ap.add_argument("--auto-tune", action="store_true",
                    help="runtime queue-depth auto-tuning from observed "
                         "consume_overlap_frac (tiered/sharded; inert on "
                         "device)")
    ap.add_argument("--route-every", type=int, default=0,
                    help="sharded: re-split replicated tables' batch "
                         "slices from observed per-replica service cost "
                         "every N batches (0 = equal slices)")
    ap.add_argument("--migrate-every", type=int, default=0,
                    help="sharded: re-plan table placement from the live "
                         "traffic window every N batches and swap it in "
                         "past --migrate-threshold (0 = off)")
    ap.add_argument("--migrate-threshold", type=float, default=1.25,
                    help="live imbalance ratio that justifies a "
                         "mid-serving placement migration")
    ap.add_argument("--warm-backing", choices=("host", "device"),
                    default="host",
                    help="tiered/sharded: warm-cache payload backing")
    ap.add_argument("--auto-budget-kib", type=int, default=0,
                    help="size hot/warm tiers from the trace under this "
                         "device budget (overrides --hot-rows/--warm-slots)")
    ap.add_argument("--hotness", choices=HOTNESS + ("all",), default="all",
                    help="run one hotness level (CI smoke) or the sweep")
    ap.add_argument("--update-every", type=int, default=0,
                    help="zero-downtime online updates: publish a "
                         "trainer-side delta every N batches and install "
                         "it mid-serving through the epoch-guarded "
                         "version stream (0 = off)")
    ap.add_argument("--update-rows", type=float, default=0.01,
                    help="fraction of rows per table each published "
                         "delta touches; past the stream's fallback "
                         "ratio a FULL snapshot lands instead")
    ap.add_argument("--tenants", type=int, default=0,
                    help="serve N tenant DLRMs over ONE shared "
                         "sharded/pool backend (TenantManager + fair-share "
                         "arbiter; 0 = single-tenant modes)")
    ap.add_argument("--trace", choices=("steady", "diurnal", "flash",
                                        "shift"), default=None,
                    help="replay a timestamped trace on a virtual clock "
                         "instead of the hotness sweep (repro.traffic)")
    ap.add_argument("--slo-p99-ms", type=float, default=0.0,
                    help="trace mode: arm the SLO controller with this "
                         "windowed-p99 target (deadline admission + "
                         "degraded-mode ladder; 0 = off)")
    ap.add_argument("--base-qps", type=float, default=0.0,
                    help="trace mode: offered base rate (0 = calibrate "
                         "to 0.5x this host's measured service rate)")
    return ap.parse_args()


def build_storage(args, model, params, stream):
    """Materialize a host-backed backend from the traffic trace through the
    protocol's build() — tier sizing explicit or planner-driven."""
    trace = stream.sample_trace(2)
    kw = dict(trace=trace)
    if model.ebc.storage.capabilities().shardable:
        kw["num_shards"] = args.shards
        kw["placement"] = args.placement
    if hasattr(model.ebc.storage, "worker_status"):    # process pool
        kw["num_workers"] = args.workers
    if args.auto_budget_kib:
        # planner-driven tier sizing from the trace coverage curve
        return model.ebc.storage.build(
            params, device_budget_bytes=args.auto_budget_kib * 1024,
            prefetch_depth=2, window_batches=16,
            async_prefetch=args.async_mode,
            warm_backing=args.warm_backing, **kw)
    return model.ebc.storage.build(
        params,
        PSConfig(hot_rows=args.hot_rows, warm_slots=args.warm_slots,
                 prefetch_depth=2, window_batches=16,
                 async_prefetch=args.async_mode,
                 warm_backing=args.warm_backing), **kw)


def print_worker_status(storage) -> None:
    """Pool backends: one operator liveness line per run — every worker
    process, its pid, and whether the heartbeat answered."""
    status_fn = getattr(storage, "worker_status", None)
    if status_fn is None:
        return
    status = status_fn()
    alive = sum(1 for w in status if w["alive"])
    cells = " ".join(
        f"w{w['worker']}:pid={w['pid']}"
        + ("" if w["alive"] else "(dead)")
        + (f":units={w['units']}" if w.get("units") is not None else "")
        for w in status)
    print(f"pool workers {alive}/{len(status)} alive  {cells}", flush=True)


def run_session(args, hotness) -> tuple[dict, int]:
    """The current API: ServingSession owns engine + loop + storage."""
    cfg = DLRMConfig(embedding=EmbeddingStageConfig(
        num_tables=args.tables, rows=args.rows, dim=128,
        pooling=args.pooling, storage=args.storage))
    model = DLRM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    stream = DLRMQueryStream(num_tables=args.tables, rows=args.rows,
                             pooling=args.pooling, batch_size=args.batch,
                             hotness=hotness, seed=0)
    device_resident = model.ebc.storage.capabilities().device_resident
    if not device_resident:
        build_storage(args, model, params, stream)
        placement = getattr(model.ebc.storage, "placement", None)
        if placement is not None:
            # the planner's shard load table (estimated from the trace)
            print(placement.describe(), flush=True)
    auto_tune = (AutoTuneConfig(
        depth_every_batches=8 if args.auto_tune else 0,
        route_every_batches=args.route_every,
        migrate_every_batches=args.migrate_every,
        migrate_threshold=args.migrate_threshold)
        if (args.auto_tune or args.route_every or args.migrate_every)
        else None)
    pub, upd_dir, rng_u = None, None, None
    controllers = None
    if args.update_every:
        # trainer side: a publisher stream over a scratch version root;
        # the session consumes it through the epoch-guarded UpdateConfig
        from repro.checkpoint import ModelUpdateStream
        from repro.serving import UpdateConfig, configure
        upd_dir = tempfile.TemporaryDirectory()
        pub = ModelUpdateStream(upd_dir.name)
        pub.publish_full(
            np.asarray(params["embedding"]["tables"])[:args.tables])
        controllers = configure(
            auto_tune=auto_tune,
            updates=UpdateConfig(stream=ModelUpdateStream(upd_dir.name)))
        auto_tune = None          # rides inside the controllers spec
        rng_u = np.random.default_rng(1)
    with ServingSession(
            model, params,
            batcher=BatcherConfig(max_batch=args.batch, max_wait_s=0.0),
            sla_ms=500,
            refresh_every_batches=(0 if device_resident
                                   else args.refresh_every),
            async_refresh=args.async_mode and not device_resident,
            auto_tune=auto_tune, controllers=controllers) as sess:
        # keep one batch queued ahead of the executing one so the generic
        # _stage_next() sees the full next batch and prefetch overlap fires
        submitted = n_batch = 0
        while submitted < args.queries:
            b = stream.next_batch()
            sess.submit_batch(b.dense, b.indices, qid0=submitted)
            submitted += args.batch
            n_batch += 1
            if submitted > args.batch:
                sess.poll()
            if pub is not None and n_batch % args.update_every == 0:
                t = (n_batch // args.update_every - 1) % args.tables
                n = max(1, int(args.update_rows * args.rows))
                rows = rng_u.choice(args.rows, size=n, replace=False)
                pub.publish_delta({t: (rows, rng_u.normal(
                    size=(n, 128)).astype(np.float32))})
        sess.drain()
        print_worker_status(model.ebc.storage)   # before close() joins them
        sess.close()    # install any in-flight async refresh before reading
        pct, viol = sess.percentiles(), sess.sla_violations()
    if upd_dir is not None:
        upd_dir.cleanup()
    return pct, viol


def run_trace(args) -> None:
    """Timestamped-trace replay (repro.traffic): deterministic offered
    load on a virtual clock, real measured service cost, optional SLO
    controller. Prints a timeline excerpt and the shed/degraded summary
    the operator guide documents."""
    from repro.serving import SLOConfig
    from repro.traffic import VirtualClock, make_traffic, replay
    cfg = DLRMConfig(embedding=EmbeddingStageConfig(
        num_tables=args.tables, rows=args.rows, dim=128,
        pooling=args.pooling, storage=args.storage))
    model = DLRM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    stream = DLRMQueryStream(num_tables=args.tables, rows=args.rows,
                             pooling=args.pooling, batch_size=args.batch,
                             hotness="med_hot", seed=0)
    device_resident = model.ebc.storage.capabilities().device_resident
    if not device_resident:
        build_storage(args, model, params, stream)
    slo = (SLOConfig(target_p99_ms=args.slo_p99_ms)
           if args.slo_p99_ms else None)
    sess = ServingSession(
        model, params,
        batcher=BatcherConfig(max_batch=args.batch, max_wait_s=0.002),
        sla_ms=500,
        refresh_every_batches=(0 if device_resident
                               else args.refresh_every),
        async_refresh=args.async_mode and not device_resident,
        slo=slo, clock=VirtualClock())
    try:
        # calibrate the real batch service time so the offered load is a
        # known multiple of what this host can serve (host-independent
        # overload); the probe batches are not traffic — drop their
        # cache footprint like warmup does
        dense = np.zeros((args.batch, cfg.dense_features), np.float32)
        idx = np.zeros((args.batch, args.tables, args.pooling), np.int32)
        t0 = time.perf_counter()
        for _ in range(3):
            np.asarray(sess._forward(dense, idx))
        t_b = (time.perf_counter() - t0) / 3
        sess.storage.flush()
        sess.storage.reset_stats()
        svc_qps = args.batch / t_b
        base = args.base_qps or 0.5 * svc_qps
        kw = dict(base_qps=base, num_tables=args.tables, rows=args.rows,
                  pooling=args.pooling, seed=0)
        if args.trace == "flash":
            kw.update(spike_qps=4.0 * svc_qps, spike_start_s=8.0 * t_b,
                      spike_len_s=24.0 * t_b)
        elif args.trace == "diurnal":
            kw.update(period_s=args.queries / base, amplitude=0.5)
        elif args.trace == "shift":
            kw.update(shift_at_s=0.5 * args.queries / base)
        gen = make_traffic(args.trace, **kw)
        window = max(32, min(256, args.queries // 2))
        rep = replay(sess, gen.queries(args.queries),
                     window_queries=window)
        reasons = dict(sess.stats.shed_reasons)
        print_worker_status(sess.storage)
    finally:
        sess.close()
    print(f"trace={args.trace} base_qps={base:.0f} "
          f"({base / svc_qps:.2f}x service rate) "
          f"slo={'off' if slo is None else f'{args.slo_p99_ms:g}ms'}")
    print("    t_ms  served   shed  qlen  wp99_ms  lvl  degraded")
    step = max(1, len(rep.timeline) // 8)
    picks = list(rep.timeline[::step])
    if rep.timeline and picks[-1] is not rep.timeline[-1]:
        picks.append(rep.timeline[-1])
    for s in picks:
        print(f"{s.t_s * 1e3:8.1f} {s.served:7d} {s.shed:6d} "
              f"{s.queue_len:5d} {s.windowed_p99_ms:8.2f} "
              f"{s.slo_level:4d} {'yes' if s.degraded else 'no':>9s}")
    pct = rep.percentiles
    line = (f"submitted={rep.submitted} admitted={rep.admitted} "
            f"served={rep.served} shed={rep.shed} "
            f"(frac={rep.shed_frac:.3f}"
            + (f", {reasons}" if reasons else "") + ") "
            f"final_wp99={rep.final_windowed_p99_ms() or 0.0:.2f}ms")
    if slo is not None:
        line += (f" breaches={pct.get('slo_breaches', 0)} "
                 f"degraded_batches={pct.get('slo_degraded_batches', 0)}")
    print(line, flush=True)


def run_tenants(args) -> None:
    """Multi-tenant serving: N DLRM tenants over ONE shared backend.

    Each tenant gets its own traffic stream; `replay_tenants` merges them
    on one virtual clock through the manager's fair scheduler, the arbiter
    re-splits device budget + prefetch depth from live per-tenant load.
    Prints one line per tenant and the shared-backend summary."""
    from repro.serving import (ArbiterConfig, SLOConfig, TenantManager,
                               TenantSpec, configure)
    from repro.traffic import VirtualClock, make_traffic, replay_tenants
    backend = args.storage
    if backend not in ("sharded", "pool"):
        print(f"tenants share one storage backend; storage={backend!r} "
              "is single-tenant — using 'sharded'", flush=True)
        backend = "sharded"
    specs, tenant_cfg = [], {}
    for t in range(args.tenants):
        # same rows/dim (shared-axis geometry), per-tenant pooling/tables
        pooling = max(2, args.pooling - 2 * t)
        cfg = DLRMConfig(embedding=EmbeddingStageConfig(
            num_tables=args.tables, rows=args.rows, dim=128,
            pooling=pooling, storage="device"))
        model = DLRM(cfg)
        specs.append(TenantSpec(name=f"t{t}", model=model,
                                params=model.init(jax.random.PRNGKey(t))))
        tenant_cfg[f"t{t}"] = cfg
    build_kw = dict(
        ps_cfg=PSConfig(hot_rows=args.hot_rows, warm_slots=args.warm_slots,
                        prefetch_depth=2, window_batches=16,
                        async_prefetch=args.async_mode,
                        warm_backing=args.warm_backing),
        num_shards=args.shards)
    if backend == "pool":
        build_kw["num_workers"] = args.workers
    mgr = TenantManager(
        specs, backend=backend,
        batcher=BatcherConfig(max_batch=args.batch, max_wait_s=0.002),
        sla_ms=500, refresh_every_batches=args.refresh_every,
        controllers=configure(
            slo=(SLOConfig(target_p99_ms=args.slo_p99_ms,
                           min_batch=max(2, args.batch // 8))
                 if args.slo_p99_ms else None),
            arbiter=ArbiterConfig(every_batches=8,
                                  budget_fallback_bytes=64 << 20)),
        scheduling="fair", clock=VirtualClock(), **build_kw)
    try:
        # calibrate offered load to the measured shared service rate
        first = mgr.session(mgr.names[0])
        dense = np.zeros((args.batch, tenant_cfg["t0"].dense_features),
                         np.float32)
        idx = np.zeros((args.batch, args.tables,
                        tenant_cfg["t0"].embedding.pooling), np.int32)
        t0 = time.perf_counter()
        for _ in range(3):
            np.asarray(first._forward(dense, idx))
        t_b = (time.perf_counter() - t0) / 3
        first.storage.reset_stats()   # probe batches are not traffic
        svc_qps = args.batch / t_b
        per_tenant = (args.base_qps or 0.5 * svc_qps) / args.tenants
        streams = {}
        for t, spec in enumerate(specs):
            cfg = tenant_cfg[spec.name]
            streams[spec.name] = make_traffic(
                "steady", base_qps=per_tenant,
                dense_features=cfg.dense_features,
                num_tables=args.tables, rows=args.rows,
                pooling=cfg.embedding.pooling,
                seed=t).queries(args.queries // args.tenants)
        reports = replay_tenants(mgr, streams)
        pct = mgr.percentiles()
        print(f"tenants={args.tenants} backend={backend} "
              f"per_tenant_qps={per_tenant:.0f} "
              f"({args.tenants * per_tenant / svc_qps:.2f}x service rate)")
        for name in mgr.names:
            rep, tp = reports[name], pct["tenants"][name]
            print(f"  {name}: submitted={rep.submitted} "
                  f"served={rep.served} shed={rep.shed} "
                  f"p50={tp['p50_ms']:.1f}ms p99={tp['p99_ms']:.1f}ms",
                  flush=True)
        shared = pct["shared"]
        total = sum(pct["tenants"][n]["served"] for n in mgr.names)
        line = (f"shared: served={total} "
                f"tenants={shared['num_tenants']}")
        st = mgr.stats()
        line += f" device_bytes={st['shared']['device_bytes']}"
        if mgr.arbiter is not None and mgr.arbiter.last_shares:
            shares = " ".join(f"{n}={s:.2f}"
                              for n, s in mgr.arbiter.last_shares.items())
            line += (f" arbiter_rounds={len(mgr.arbiter.events)} "
                     f"shares[{shares}]")
        print(line, flush=True)
        print_worker_status(mgr.shared)
    finally:
        mgr.close()


def main():
    args = parse_args()
    enable_compile_cache()
    if args.slo_p99_ms and not (args.trace or args.tenants):
        raise SystemExit("--slo-p99-ms needs --trace or --tenants: the SLO "
                         "controller watches windowed p99 over a "
                         "timestamped replay")
    if args.tenants:
        if args.trace:
            raise SystemExit("--tenants replays per-tenant steady streams; "
                             "drop --trace (the multi_tenant bench sweep "
                             "covers mixed profiles)")
        run_tenants(args)
        return
    if args.trace:
        run_trace(args)
        return
    levels = HOTNESS if args.hotness == "all" else (args.hotness,)
    for hotness in levels:
        pct, viol = run_session(args, hotness)
        line = (f"{hotness:9s} served={pct['served']:4d} "
                f"p50={pct['p50_ms']:.1f}ms p99={pct['p99_ms']:.1f}ms "
                f"batch={pct['mean_batch_ms']:.1f}ms "
                f"sla_viol={viol}")
        if "cache_hit_rate" in pct:
            line += (f" hit={pct['cache_hit_rate']:.2f} "
                     f"(hot={pct['hot_hit_rate']:.2f} "
                     f"warm={pct['warm_hit_rate']:.2f}) "
                     f"evict={pct['evictions']} "
                     f"refresh={pct['refreshes']} "
                     f"off_crit={pct['off_critical_frac']:.2f}")
            if "prefetch_depth" in pct:
                line += (f" depth={pct['prefetch_depth']} "
                         f"(retunes={pct['depth_retunes']})")
            if "migrations" in pct:
                line += f" migrations={pct['migrations']}"
            if "routing_updates" in pct:
                line += f" reroutes={pct['routing_updates']}"
        if "model_version" in pct:
            line += (f" v={pct['model_version']} "
                     f"updates={pct['updates_applied']}"
                     f"(d={pct['updates_delta']} f={pct['updates_full']} "
                     f"rb={pct['updates_rolled_back']}) "
                     f"stall={pct['update_stall_s'] * 1e3:.1f}ms")
        print(line, flush=True)


if __name__ == "__main__":
    main()
