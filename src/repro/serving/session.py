"""ServingSession — the one-stop facade over batcher + engine + storage.

PR 1–2 exposed the embedding-serving machinery through three divergent
surfaces (`EmbeddingBagCollection(storage=...)`, the `ParameterServer`
stack, and a hand-wired `InferenceServer` loop). A session owns all three
and wires them from the storage backend's capability descriptor alone:

  * **engine** — device-resident backends get one fully-jitted forward;
    host-backed backends get the split engine (host `lookup()` feeding the
    jitted post-embedding remainder), the shape every backend's lookup
    contract guarantees is bit-exact.
  * **loop** — an `InferenceServer` drives prefetch staging and (async)
    hot-set refresh purely through the `EmbeddingStorage` protocol, so any
    async-capable backend reports `off_critical_frac`/cache stats with no
    backend-specific serving code.
  * **lifecycle** — warmup compiles the engine then `flush()` +
    `reset_stats()` so synthetic traffic never pollutes the caches;
    `close()` installs in-flight refresh plans and joins every worker.

Typical use (see docs/serving.md for the operator guide):

    model = DLRM(cfg)                       # cfg.embedding.storage="sharded"
    params = model.init(rng)
    model.ebc.storage.build(params, ps_cfg, trace=trace, num_shards=4)
    with ServingSession(model, params,
                        batcher=BatcherConfig(max_batch=64),
                        refresh_every_batches=8,
                        async_refresh=True) as sess:
        sess.submit(query); ...; sess.poll(); ...
        print(sess.percentiles())
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.ps.tuning import AutoTuneConfig, AutoTuner
from repro.serving.config import ServingControllers, resolve_controllers
from repro.serving.server import (BatcherConfig, InferenceServer, Query,
                                  QueryShedError)
from repro.serving.slo import SLOConfig, SLOController
from repro.storage import require_capability


class ServingSession:
    """Owns batcher + engine + storage for one model; drives overlap
    generically through the `EmbeddingStorage` protocol."""

    def __init__(self, model, params: dict, *,
                 batcher: Optional[BatcherConfig] = None,
                 sla_ms: float = 50.0,
                 refresh_every_batches: int = 0,
                 async_refresh: bool = False,
                 auto_tune: Union[AutoTuneConfig, bool, None] = None,
                 slo: Optional[SLOConfig] = None,
                 controllers: Optional[ServingControllers] = None,
                 clock: Optional[Callable] = None,
                 warmup: bool = True):
        # auto_tune=/slo= are exact aliases for controllers=configure(...)
        # — one surface per call, never both (ValueError)
        spec = resolve_controllers(controllers, auto_tune, slo,
                                   where="ServingSession")
        if spec.arbiter is not None:
            raise ValueError(
                "the arbiter re-splits shared capacity ACROSS tenants; a "
                "single-model ServingSession has nothing to arbitrate — "
                "pass ArbiterConfig through TenantManager(controllers=...)")
        auto_tune, slo = spec.auto_tune, spec.slo
        self.model = model
        self.params = params
        self.storage = model.ebc.storage
        self.clock = clock
        caps = self.storage.capabilities()
        # online model updates (epoch guard): every admitted query is
        # pinned to the version current at its admission, and the stream
        # is polled between batches — see _apply_updates for the barrier
        self._updates = spec.updates
        self._model_version = 0
        self._updates_applied = 0
        self._updates_delta = 0
        self._updates_full = 0
        self._updates_rolled_back = 0
        self._update_stall_s = 0.0
        self._update_batches = 0
        self._pending_updates: list = []
        self._qid_versions: dict[int, int] = {}
        if self._updates is not None:
            require_capability(self.storage, "updatable")
            if caps.device_resident:
                # device updates mutate the bound params' tables — bind
                # THIS session's dict so a commit swaps the very object
                # the engine reads each call
                self.storage.build(self.params)
            self._model_version = self.storage.version()
        if (async_refresh or refresh_every_batches) and not caps.refreshable:
            # fail fast instead of silently never re-pinning
            require_capability(self.storage, "refreshable")
        batcher = batcher if batcher is not None else BatcherConfig()
        if (slo is not None and slo.shed_deadline_frac > 0
                and batcher.deadline_ms == 0):
            # an SLO without admission control cannot hold its target —
            # the backlog's queueing delay alone blows it. Default the
            # deadline budget to the target unless the caller configured
            # (or explicitly zeroed) one.
            batcher = dataclasses.replace(
                batcher,
                deadline_ms=slo.target_p99_ms * slo.shed_deadline_frac)
        self.server = InferenceServer(
            self._build_engine(caps), batcher, sla_ms=sla_ms,
            storage=self.storage,
            refresh_every_batches=refresh_every_batches,
            async_refresh=async_refresh, clock=clock,
            # the shrink rung re-sizes the batch quantum mid-overload; its
            # sizes join the batch shapes warmed below, so engaging the
            # ladder never stalls a breached window on XLA compilation
            min_batch=slo.min_batch if slo is not None else 0)
        self._forward = self.server.forward
        self._closed = False
        self._next_qid = 0
        if warmup:
            self._warmup()
        # runtime auto-tuning (queue depth / tier capacity): driven from
        # poll() through protocol verbs only. Backends that do not report
        # `tunable` (device) leave the tuner permanently inert — asking for
        # tuning on them is a no-op by design, not an error. Created AFTER
        # warmup: the tuner's first counter snapshot must postdate the
        # warmup stats reset or the first window sees negative deltas.
        if auto_tune is True:
            auto_tune = AutoTuneConfig()
        self.tuner: Optional[AutoTuner] = (
            AutoTuner(auto_tune, self.storage) if auto_tune else None)
        # SLO outer loop (serving/slo.py): windowed-p99 watcher + overload
        # escalation ladder. Also created after warmup, handed the tuner
        # so it can suspend the queue-depth leg while engaged, and the
        # live Batcher so the shrink rung (min_batch > 0) can re-size it.
        self.slo: Optional[SLOController] = (
            SLOController(slo, self.storage, self.server.stats,
                          tuner=self.tuner, batcher=self.server.batcher)
            if slo is not None else None)

    # -- engine -------------------------------------------------------------
    def _build_engine(self, caps):
        """Pick the forward shape from the capability descriptor — the only
        place residency is ever consulted. `self.engine_jit` keeps the
        jitted program each batch runs (the whole forward taking
        `(params, dense, indices)` when device-resident, else the
        post-lookup remainder taking `(dense, pooled)`), so a caller can
        lower it and inspect what was compiled."""
        model, params = self.model, self.params
        if caps.device_resident:
            # params ride as a per-call ARGUMENT, not a closure capture: a
            # closed-over array is baked into the jaxpr as a constant, so
            # an online update (which swaps params["tables"] inside this
            # dict) would be invisible to the compiled engine forever
            jitted = jax.jit(lambda p, d, i: model.forward(p, d, i))
            self.engine_jit = jitted

            def forward(dense, idx):
                # the batch's host→device copy, explicit so that its time
                # is a span of its own. The engine is dispatched as soon
                # as the copy is issued, so that it starts the moment the
                # copy lands; the span then waits for the copy alone.
                # (Waiting before the dispatch cost 1–3 ms a batch on a
                # v5e.) `lookups` counts the row ids copied, padding
                # queries included.
                with TraceAnnotation("serve.put",
                                     bytes=dense.nbytes + idx.nbytes,
                                     lookups=idx.size):
                    dense, idx = jax.device_put((dense, idx))
                    scores = jitted(self.params, dense, idx)
                    jax.block_until_ready((dense, idx))
                return scores
            return forward
        rest = jax.jit(lambda d, p: model.forward_from_pooled(params, d, p))
        self.engine_jit = rest

        def forward(dense, idx):
            with TraceAnnotation("serve.lookup"):
                pooled = model.ebc.apply(params, idx)   # host lookup
            return rest(jnp.asarray(dense), pooled)  # jitted remainder
        return forward

    def _warmup(self) -> None:
        """Compile the engine on a zero batch per batch shape the server
        pads to (`InferenceServer.shapes`), so that no batch compiles
        while serving, then drop the synthetic traffic's footprint
        (warm-cache entries, refresh-window batch) and its counters so
        measurements start clean."""
        cfg = self.model.cfg
        for batch in self.server.shapes:
            dense = np.zeros((batch, cfg.dense_features), np.float32)
            idx = np.zeros((batch, *cfg.embedding.query_index_shape()),
                           np.int32)
            jax.block_until_ready(self._forward(dense, idx))
        self.storage.flush()
        self.storage.reset_stats()

    # -- serving loop (delegation) ------------------------------------------
    def submit(self, query: Query) -> None:
        self.server.submit(query)
        # admission is the pin point: the query is guaranteed to be served
        # by THIS version (the commit barrier drains it before any swap).
        # A shed query raises above and is never pinned.
        if self._updates is not None:
            self._qid_versions[query.qid] = self._model_version
        # keep the auto-advancing submit_batch counter ahead of manually
        # assigned qids so mixing the two surfaces never reuses an id
        self._next_qid = max(self._next_qid, query.qid + 1)

    def submit_batch(self, dense: np.ndarray, indices: np.ndarray,
                     qid0: Optional[int] = None) -> int:
        """Convenience: enqueue one [B, ...] batch as B queries; returns
        how many were ADMITTED. Shed queries (admission control on an
        overloaded queue) are counted in `stats.shed_queries` rather than
        raised per query — callers who need the typed rejection submit
        single queries through `submit()`.

        Query ids auto-advance from the last issued one, so consecutive
        calls never emit duplicate qids into latency accounting (the old
        `qid0=0` default made every batch reuse ids 0..B-1). Passing an
        explicit `qid0` re-bases the counter."""
        if qid0 is None:
            qid0 = self._next_qid
        admitted = 0
        for i in range(len(dense)):
            try:
                self.server.submit(Query(qid=qid0 + i, dense=dense[i],
                                         indices=indices[i]))
                admitted += 1
                if self._updates is not None:
                    self._qid_versions[qid0 + i] = self._model_version
            except QueryShedError:
                pass            # tallied in stats by the server
        self._next_qid = qid0 + len(dense)
        return admitted

    def poll(self, force: bool = False) -> int:
        served = self.server.poll(force=force)
        if served:
            # SLO first: it publishes depth ownership (suspension) before
            # the tuner decides whether its depth leg may fire this batch
            if self.slo is not None:
                self.slo.step()
            if self.tuner is not None:
                self.tuner.step()   # one executed batch per serving poll
            if self._updates is not None:
                self._update_batches += 1
                if self._update_batches \
                        % self._updates.poll_every_batches == 0:
                    self._apply_updates()
        return served

    # -- online model updates ------------------------------------------------
    def version_of(self, qid: int) -> Optional[int]:
        """The model version `qid` was pinned to at admission (None when
        updates are not armed or the qid was never admitted). The epoch
        guard guarantees the response for `qid` is bit-exact under this
        version's tables."""
        return self._qid_versions.get(qid)

    def _apply_updates(self) -> None:
        """Poll the update stream; publish any new versions behind the
        epoch guard. Runs between batches on the serving thread.

        The commit barrier comes first: every queued query was admitted —
        and pinned — under the CURRENT version, so they are force-served
        through the raw server poll (no recursion into this hook) before
        any tier takes new bytes. Only then do the records apply, in
        version order, through the storage update transaction. A
        distributed rollback (a pool worker killed mid-commit) leaves the
        record pending for the next poll — versions never apply out of
        order, and the stream cursor is never replayed."""
        records = self._pending_updates \
            + list(self._updates.stream.poll())
        self._pending_updates = []
        if not records:
            return
        t0 = time.perf_counter()
        deadline = t0 + self._updates.drain_timeout_s
        while self.server.batcher.queue and time.perf_counter() < deadline:
            self.server.poll(force=True)
        for i, rec in enumerate(records):
            v = int(rec["version"])
            self.storage.begin_update(v)
            for t, (rows, vals) in rec["tables"].items():
                self.storage.apply_update(int(t), rows, vals)
            res = self.storage.commit_update(v)
            if not res.get("updated"):
                self._updates_rolled_back += 1
                self._pending_updates = records[i:]
                break
            self._model_version = v
            self._updates_applied += 1
            if rec.get("kind") == "delta":
                self._updates_delta += 1
            else:
                self._updates_full += 1
        self._update_stall_s += time.perf_counter() - t0

    def drain(self, timeout_s: float = 10.0) -> None:
        """`InferenceServer.drain` routed through `self.poll` so the
        auto-tuner sees drain-phase batches too (same force-flush law)."""
        self.server.drain(timeout_s=timeout_s, poll=self.poll)

    # -- reporting ----------------------------------------------------------
    @property
    def stats(self):
        return self.server.stats

    def percentiles(self) -> dict:
        """Latency percentiles + whatever cache/overlap counters the bound
        backend reports (`off_critical_frac` et al. for any async-capable
        backend) — no backend-specific keys wired here. When auto-tuning
        ran, the tuner's summary (`prefetch_depth`, `depth_retunes`, ...)
        rides along."""
        out = self.server.stats.percentiles()
        if self.tuner is not None and out:
            out.update(self.tuner.summary())
        if self.slo is not None and out:
            out.update(self.slo.summary())
        if self._updates is not None and out:
            out["model_version"] = self._model_version
            out["updates_applied"] = self._updates_applied
            out["updates_delta"] = self._updates_delta
            out["updates_full"] = self._updates_full
            out["updates_rolled_back"] = self._updates_rolled_back
            out["update_stall_s"] = float(self._update_stall_s)
        return out

    def sla_violations(self) -> int:
        return self.server.sla_violations()

    # -- lifecycle ----------------------------------------------------------
    def close(self) -> None:
        """Install any in-flight refresh plan, stop the refresh helper,
        then close the storage backend (prefetch workers, shard pools).
        Idempotent."""
        if self._closed:
            return
        self._closed = True
        try:
            self.server.close()
        finally:
            self.storage.close()

    def __enter__(self) -> "ServingSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
