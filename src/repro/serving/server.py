"""DLRM inference serving loop (paper §II-A deployment shape).

Queries arrive, a batcher groups them (the paper uses large batches of 2048
to saturate the GPU; same logic here), the engine executes the forward pass,
and per-query latencies are tracked against an SLA target. Percentile
reporting mirrors how the paper reports batch latency.

Dispatch-ahead (see docs/serving.md, "When a poll dispatches ahead"):
with a device-resident engine on an accelerator and the real clock, a
poll dispatches the next ready batch before it waits for the current
one's logits, so the device has the next batch queued while the host
records, returns and assembles.

Storage integration (see docs/serving.md): the server drives any
`repro.storage.EmbeddingStorage` backend generically through the protocol —
no backend-specific code in the loop, so every current and future backend
gets the two overlap mechanisms for free:
  * prefetch: before each forward, the NEXT pending full batch's cache
    misses are staged (`storage.stage`, guarded by the `storage.can_stage`
    backpressure probe); async-capable backends resolve the gathers on
    their own worker threads.
  * refresh: every `refresh_every_batches` executed batches the hot set is
    re-planned. With `async_refresh=True` the pure planning phase
    (`storage.plan_refresh` over a `storage.refresh_window()` snapshot)
    runs on a helper thread and `poll()` installs the result on a later
    iteration (`storage.install_refresh`) — re-pinning leaves the critical
    path too.

Prefer the `repro.serving.session.ServingSession` facade, which wires the
forward engine, warmup, and storage lifecycle around this loop. (The PR-2
`ps=` deprecation shim is gone: pass `storage=ebc.storage`, or a
`TieredStorage.adopt(ps)` wrapper for a raw server.)
"""
from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import itertools
import time
from typing import Callable, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class Query:
    qid: int
    dense: np.ndarray          # [F]
    # int32 row ids, in the layout the embedding configuration states
    # (EmbeddingStageConfig.query_index_shape): [T, L] when every table
    # has bags of L rows; the flat [sum(L)] when bag sizes are given per
    # table, table t's bag in columns sum(L[:t]):sum(L[:t + 1])
    indices: np.ndarray
    # None = stamped by the batcher at submit time (live traffic); replay
    # drivers preset the trace's nominal arrival so latency accounting
    # reflects offered load even when the server is behind
    arrival_s: Optional[float] = None


@dataclasses.dataclass
class BatcherConfig:
    max_batch: int = 2048
    max_wait_s: float = 0.002   # SLA-driven batching window
    # admission control (overload shedding); both default OFF so steady
    # state is untouched:
    # hard bound on queued queries — submit() sheds (typed rejection)
    # instead of letting arrivals outpace service without backpressure
    max_queue: int = 0          # 0 = unbounded
    # per-query deadline budget: shed at submit when the predicted wait
    # (queued batches ahead x EWMA batch service time) already blows it
    deadline_ms: float = 0.0    # 0 = off


class QueryShedError(RuntimeError):
    """Typed admission rejection — a shed query is never silently dropped.

    Raised by `Batcher.submit` when admission control rejects a query;
    carries enough context for the caller to retry elsewhere or count the
    loss. `reason` is `"queue_full"` (max_queue bound) or `"deadline"`
    (predicted wait exceeds the deadline budget)."""

    def __init__(self, qid: int, reason: str, queue_len: int,
                 predicted_wait_s: Optional[float] = None):
        self.qid = qid
        self.reason = reason
        self.queue_len = queue_len
        self.predicted_wait_s = predicted_wait_s
        wait = ("" if predicted_wait_s is None
                else f", predicted wait {predicted_wait_s * 1e3:.1f}ms")
        super().__init__(f"query {qid} shed ({reason}; "
                         f"queue_len={queue_len}{wait})")


class Batcher:
    """Groups queries into batches; owns the admission-control decision.

    `clock` abstracts time for the batching window and arrival stamps —
    the default is the real `time.perf_counter`; replay harnesses pass a
    `repro.traffic.VirtualClock` so offered load is deterministic.

    `queue` holds every admitted query not answered yet, oldest first. A
    batch `next_batch` hands out stays at the head of it, in flight,
    until `answered` takes it off, so an empty queue means everything
    admitted was answered. `in_flight` counts those queries.
    """

    #: EWMA smoothing for the observed batch service time (deadline
    #: admission). One observation per executed batch; 0.3 tracks load
    #: shifts within a few batches without chasing single-batch noise.
    SERVICE_EWMA_ALPHA = 0.3

    def __init__(self, cfg: BatcherConfig, clock: Optional[Callable] = None):
        self.cfg = cfg
        self.clock = clock if clock is not None else time.perf_counter
        self.queue: collections.deque[Query] = collections.deque()
        self.in_flight = 0
        self.shed = 0
        self.shed_reasons: collections.Counter = collections.Counter()
        self.service_ewma_s: Optional[float] = None

    def observe_service(self, dt_s: float) -> None:
        """One executed batch took `dt_s` seconds — feed the service-time
        EWMA the deadline admission predicts waits from."""
        a = self.SERVICE_EWMA_ALPHA
        self.service_ewma_s = (dt_s if self.service_ewma_s is None
                               else a * dt_s + (1 - a) * self.service_ewma_s)

    def _admit(self, q: Query) -> None:
        """Shed (raise) instead of queueing when admission control says the
        query cannot be served usefully: the queue bound is hit, or the
        predicted wait to its batch's completion already exceeds the
        deadline budget. Runs BEFORE the query is queued, so a shed query
        costs no assembly or service work at all. Both count the queries
        in flight: they are admitted and not answered yet."""
        cfg = self.cfg
        qlen = len(self.queue)
        if cfg.max_queue and qlen >= cfg.max_queue:
            self.shed += 1
            self.shed_reasons["queue_full"] += 1
            raise QueryShedError(q.qid, "queue_full", qlen)
        if cfg.deadline_ms and self.service_ewma_s is not None:
            # whole batches queued AHEAD of this query. Its own batch's
            # service deliberately doesn't count: an empty queue must
            # always admit, or one slow batch (compile, GC) could push the
            # EWMA past the deadline and wedge admission shut forever —
            # nothing served means the estimate never refreshes
            batches_ahead = qlen // cfg.max_batch
            wait = batches_ahead * self.service_ewma_s
            if wait > cfg.deadline_ms / 1e3:
                self.shed += 1
                self.shed_reasons["deadline"] += 1
                raise QueryShedError(q.qid, "deadline", qlen, wait)

    def submit(self, q: Query) -> None:
        self._admit(q)
        if q.arrival_s is None:
            q.arrival_s = self.clock()
        self.queue.append(q)

    def waiting(self) -> int:
        """Queries admitted and not handed out yet."""
        return len(self.queue) - self.in_flight

    def next_batch(self, force: bool = False) -> Optional[list[Query]]:
        """Hand out the oldest waiting queries: a full batch, or a partial
        one once the oldest waiting query's batching window has elapsed.
        `force=True` flushes a partial batch immediately (drain/shutdown
        path). The batch stays queued until `answered`."""
        waiting = self.waiting()
        if not waiting:
            return None
        head = self.queue[self.in_flight]
        if (not force and waiting < self.cfg.max_batch
                and self.clock() < head.arrival_s + self.cfg.max_wait_s):
            return None
        out = list(itertools.islice(self.queue, self.in_flight,
                                    self.in_flight + self.cfg.max_batch))
        self.in_flight += len(out)
        return out

    def answered(self, n: int) -> None:
        """The oldest `n` queries handed out were answered (or given up):
        they leave the queue."""
        for _ in range(n):
            self.queue.popleft()
        self.in_flight -= n


#: Halvings of `max_batch` a handed-out batch may be padded to: the
#: batch shapes run from `max_batch` down to `max_batch // 8`.
SHAPE_HALVINGS = 3


def batch_shapes(max_batch: int, min_batch: int = 0) -> tuple[int, ...]:
    """The batch sizes the engine runs, largest first, each compiled once
    in set-up: `max_batch` and its halvings down to
    `max_batch >> SHAPE_HALVINGS` (at least 1), and where an SLO shrink
    rung is armed (`min_batch > 0`) every `max_batch` the rung can set,
    its halvings floored at `min_batch` (`SLOController._shrink`).
    `InferenceServer._hand_out` pads a batch to the smallest that holds
    it, so a partial batch of an open loop does not pay the kernel and
    the copy for a full batch of zero rows."""
    sizes = {max(1, max_batch >> k) for k in range(SHAPE_HALVINGS + 1)}
    b = max_batch
    while 0 < min_batch < b:
        b = max(min_batch, b // 2)
        sizes.add(b)
    return tuple(sorted(sizes, reverse=True))


def _device_beside_host() -> bool:
    """Whether JAX's default device is an accelerator of its own. On the
    CPU backend the engine computes on the host's own cores, the ones the
    serving loop's host work runs on, so a batch dispatched ahead has no
    separate device to keep busy: the poll stays serial there."""
    return jax.default_backend() != "cpu"


@dataclasses.dataclass
class _Flight:
    """A batch handed out by the batcher and not answered yet: the time
    it was handed out, its device batch size, and once dispatched, the
    engine's scores (still being computed while the device runs it)."""
    batch: list
    padded: int
    popped: float
    scores: object = None


@dataclasses.dataclass
class ServeStats:
    served: int = 0
    batch_latencies_s: list = dataclasses.field(default_factory=list)
    query_latencies_s: list = dataclasses.field(default_factory=list)
    # refreshes whose planning phase ran on the helper thread
    async_refreshes: int = 0
    # admission control: queries shed at submit (typed rejections, by
    # reason) and the request-queue length gauge, mirrored from the
    # batcher after every submit/poll
    shed_queries: int = 0
    shed_reasons: dict = dataclasses.field(default_factory=dict)
    request_queue_len: int = 0
    # storage-backend cache counters (tiered / sharded / any backend whose
    # stats() reports them): hot/warm hit rates, cold misses, evictions,
    # refreshes, and the prefetch queue/overlap counters — updated by
    # InferenceServer.poll() after every executed batch. Empty for
    # stats-free backends (device).
    ps_stats: dict = dataclasses.field(default_factory=dict)

    _PS_KEYS = ("hot_hit_rate", "warm_hit_rate", "cache_hit_rate",
                "cold_miss_rate", "hot_hits", "warm_hits", "cold_misses",
                "evictions", "refreshes", "prefetch_hits",
                # queue / overlap counters (async + sync staging)
                "queue_depth", "max_queue_depth", "off_critical_frac",
                "consume_ready", "consume_waited", "consume_wait_s",
                "consume_overlap_frac",
                # degraded (warm-cache-only) serving counters + the exact
                # L2 error of the zero-filled accesses vs the dense gather
                "degraded_lookups", "degraded_rows", "degraded_l2_delta")

    def percentiles(self) -> dict:
        """Latency percentiles plus (when a PS is attached) the cache and
        overlap counters whitelisted in `_PS_KEYS`. `off_critical_frac` is
        the fraction of cold-missed rows whose host gather never ran on the
        lookup critical path — the headline overlap metric."""
        if not self.query_latencies_s:
            return {}
        q = np.asarray(self.query_latencies_s) * 1e3
        b = np.asarray(self.batch_latencies_s) * 1e3
        out = {"p50_ms": float(np.percentile(q, 50)),
               "p95_ms": float(np.percentile(q, 95)),
               "p99_ms": float(np.percentile(q, 99)),
               "mean_batch_ms": float(b.mean()),
               "served": self.served}
        # admission gauges ride along unconditionally: an operator reading
        # shed_queries == 0 learns shedding is armed-but-idle, which a
        # missing key cannot say
        out["shed_queries"] = self.shed_queries
        out["request_queue_len"] = self.request_queue_len
        for k in self._PS_KEYS:
            if k in self.ps_stats:
                out[k] = self.ps_stats[k]
        if self.async_refreshes:
            out["async_refreshes"] = self.async_refreshes
        return out


class InferenceServer:
    """forward(dense [B,F], indices [B,T,L] or [B,sum(L)]) -> scores [B].

    Pass the model's storage backend as `storage` (any
    `repro.storage.EmbeddingStorage`): the server then (a) stages the NEXT
    pending batch's cache misses before executing the current one
    (prefetch overlap), (b) re-plans the hot set every
    `refresh_every_batches` executed batches from the backend's sliding
    traffic window (paper §IV-C periodic re-pinning) — on a helper thread
    when `async_refresh=True` — and (c) mirrors the backend's cache +
    overlap counters into `stats.percentiles()`. All of it goes through
    the protocol verbs, so backends that cannot stage or refresh degrade
    to no-ops instead of needing special cases here. (The PR-2 `ps=`
    spelling is gone; pass `storage=ebc.storage` — docs/serving.md has
    the migration table.)
    """

    def __init__(self, forward: Callable, batcher_cfg: BatcherConfig,
                 sla_ms: float = 50.0, storage=None,
                 refresh_every_batches: int = 0,
                 async_refresh: bool = False,
                 clock: Optional[Callable] = None,
                 min_batch: int = 0):
        self.forward = forward
        # `clock` abstracts serving time: None = real time.perf_counter;
        # a replay harness passes a `repro.traffic.VirtualClock` (callable
        # with an `advance()` method) so latencies are measured in trace
        # time — real batch service durations advance the virtual clock
        self.clock = clock if clock is not None else time.perf_counter
        self._clock_advance = getattr(clock, "advance", None)
        self.batcher = Batcher(batcher_cfg, clock=self.clock)
        # the batch sizes a handed-out batch is padded to, the SLO shrink
        # rung's among them when one is armed down to `min_batch`
        # (`batch_shapes`); a session compiles each of them in set-up
        self.shapes = batch_shapes(batcher_cfg.max_batch, min_batch)
        self.sla_s = sla_ms / 1e3
        self.stats = ServeStats()
        self.storage = storage
        if (async_refresh and storage is not None
                and not storage.capabilities().refreshable):
            from repro.storage import require_capability
            require_capability(storage, "refreshable")
        self.refresh_every_batches = refresh_every_batches
        self.async_refresh = async_refresh
        self._executed_batches = 0
        self._refresh_pool: Optional[
            concurrent.futures.ThreadPoolExecutor] = None
        self._refresh_future: Optional[concurrent.futures.Future] = None
        # optional response tap: called with (batch, scores[:len(batch)])
        # after every executed batch, outside the timed region. The
        # online-update bench uses it to check each response bit-exactly
        # against the model version its query was pinned to.
        self.on_batch: Optional[Callable] = None
        # dispatch-ahead (see poll): only where the engine's forward is
        # asynchronous (device-resident storage), runs on a device beside
        # the host, and time is real. A virtual clock advances by serial
        # service time, and host-backed lookups run synchronously, in
        # step with their refresh schedule.
        self._ahead_ok = (self._clock_advance is None
                          and storage is not None
                          and storage.capabilities().device_resident
                          and _device_beside_host())
        self._in_flight: Optional[_Flight] = None

    def submit(self, q: Query) -> None:
        """Admit or shed one query. A shed query raises `QueryShedError`
        (typed, never silent); either way the admission gauges mirror into
        stats so `percentiles()` reflects sheds that happened between
        polls."""
        try:
            self.batcher.submit(q)
        finally:
            self.stats.shed_queries = self.batcher.shed
            self.stats.shed_reasons = dict(self.batcher.shed_reasons)
            self.stats.request_queue_len = len(self.batcher.queue)

    @staticmethod
    def _assemble_indices(batch: list[Query], b: int) -> np.ndarray:
        """[b, T, L] or flat [b, sum(L)] int32 index tensor, the queries'
        own layout; rows past len(batch) stay zero
        (the padding hint_valid() later excludes from PS stats). Shared by
        _assemble and _stage_next so staged indices always match the
        upcoming lookup's bit-for-bit (consume() matches on equality)."""
        idx = np.zeros((b,) + batch[0].indices.shape, np.int32)
        for i, q in enumerate(batch):
            idx[i] = q.indices
        return idx

    def _assemble(self, batch: list[Query], b: int):
        """[b, F] dense and [b, ...] indices; rows past len(batch) stay
        zero."""
        dense = np.zeros((b,) + batch[0].dense.shape, np.float32)
        for i, q in enumerate(batch):
            dense[i] = q.dense
        return dense, self._assemble_indices(batch, b)

    def _stage_next(self) -> None:
        """Prefetch: resolve the next FULL pending batch's cold misses now,
        so its host gathers overlap the current batch's compute. Only a
        full batch is staged — its contents are then FIFO-deterministic, so
        the staged indices exactly match the upcoming lookup. Backpressure
        is checked before any assembly work, and only the indices are
        assembled (staging never needs the dense features). The queries
        in flight are skipped: they are already looked up."""
        batcher = self.batcher
        b = batcher.cfg.max_batch
        if batcher.waiting() < b or not self.storage.can_stage():
            return
        nxt = list(itertools.islice(batcher.queue, batcher.in_flight,
                                    batcher.in_flight + b))
        self.storage.stage(self._assemble_indices(nxt, b))

    # -- async refresh driver -----------------------------------------------
    def _start_refresh(self) -> None:
        """Kick off re-pinning. Sync mode blocks here (PR-1 behaviour);
        async mode snapshots the traffic window on this thread and plans on
        a helper, leaving installation to a later poll()."""
        if not self.async_refresh:
            self.storage.refresh()
            return
        if self._refresh_future is not None:    # previous plan still in
            return                              # flight: don't pile up
        if self._refresh_pool is None:
            self._refresh_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="ps-refresh")
        window = self.storage.refresh_window()  # snapshot on serving thread
        self._refresh_future = self._refresh_pool.submit(
            self.storage.plan_refresh, window)

    def _install_refresh_if_ready(self) -> None:
        """Install a finished helper-thread plan (serving thread only —
        install_refresh mutates tier state). Planner exceptions re-raise
        here, on the serving thread."""
        if self._refresh_future is not None and self._refresh_future.done():
            self._install_pending_refresh()

    def _install_pending_refresh(self) -> None:
        """Take the in-flight future (blocking if unfinished), install its
        plan — a None plan still applies the scheduled warm-tier decay,
        exactly like a sync refresh — count a real re-pin, and re-mirror
        PS stats. Shared by the poll() path and close()."""
        fut, self._refresh_future = self._refresh_future, None
        if self.storage.install_refresh(fut.result())["replanned"]:
            self.stats.async_refreshes += 1
        self.stats.ps_stats = self.storage.stats()

    def poll(self, force: bool = False) -> int:
        """Answer at most one batch; returns #queries served.

        The batch answered is the one in flight, if any, else the one the
        batcher hands out now. Where dispatch-ahead is sound (a
        device-resident engine on an accelerator, on the real clock) and
        `force` is not set, the poll first assembles, copies and
        dispatches the NEXT batch, if the batcher would hand one out now,
        so that it is queued on the device behind the answered one and the
        host's work between batches overlaps the device's. That batch
        stays in flight, and its queries in `batcher.queue`, until the
        next poll answers it.

        A poll that serves a batch runs under the profiler span
        `serve.batch`, whose children split it: `serve.assemble` and
        `serve.stage` for each batch dispatched, `serve.forward` (exactly
        the batch service time: the dispatches, then the wait for the
        answered batch's logits) and `serve.record`. Their statistics are
        per-batch sums, so no span is opened per query; with no profiler
        running a span costs about a microsecond (docs/serving.md,
        "Tracing a served batch")."""
        cur = self._in_flight
        if cur is None:
            cur = self._hand_out(force)
            if cur is None:
                return 0
        nxt = None if force or not self._ahead_ok else self._hand_out()
        self._in_flight = nxt
        batch = cur.batch
        n = len(batch)
        try:
            # queue waits as sums: the batch is FIFO, so its head waited
            # most
            with TraceAnnotation(
                    "serve.batch", queries=n, padded=cur.padded,
                    wait_s_sum=n * cur.popped
                    - sum(q.arrival_s for q in batch),
                    wait_s_max=cur.popped - batch[0].arrival_s,
                    ahead=int(nxt is not None)):
                return self._serve(cur, nxt)
        except BaseException:
            # give up what was handed out, as a serial poll always did
            # with the batch it held: those queries leave the queue
            # unanswered, and a later poll starts afresh
            self._in_flight = None
            self.batcher.answered(self.batcher.in_flight)
            raise

    def _hand_out(self, force: bool = False) -> Optional[_Flight]:
        batch = self.batcher.next_batch(force=force)
        if not batch:
            return None
        # the smallest shape that holds the batch; never past the live
        # max_batch, so a full batch runs at max_batch, the size
        # `_stage_next` stages it at
        limit = self.batcher.cfg.max_batch
        padded = min((s for s in self.shapes if len(batch) <= s <= limit),
                     default=limit)
        return _Flight(batch, padded, self.clock())

    def _prepare(self, flight: _Flight):
        """Assemble a batch about to be dispatched and run the storage
        hooks before its lookup; its dense and index arrays."""
        n = len(flight.batch)
        with TraceAnnotation("serve.assemble"):
            arrays = self._assemble(flight.batch, flight.padded)
        if self.storage is not None:
            # both run outside the timed region. Install a finished
            # refresh FIRST so staging probes the post-refresh tier state
            # (staging against the old plan would prefetch rows about to
            # become hot and skip warm rows about to be invalidated).
            with TraceAnnotation("serve.stage"):
                self._install_refresh_if_ready()
                # staging models work that overlaps the PREVIOUS batch's
                # compute, so it must not bill this batch
                self._stage_next()
                # batcher padding is not traffic — keep it out of cache
                # stats and the refresh window
                self.storage.hint_valid(n)
        return arrays

    def _serve(self, cur: _Flight, nxt: Optional[_Flight]) -> int:
        batch = cur.batch
        n = len(batch)
        todo = [f for f in (cur, nxt) if f is not None and f.scores is None]
        arrays = [self._prepare(f) for f in todo]
        with TraceAnnotation("serve.forward"):
            t0 = time.perf_counter()
            for f, (dense, idx) in zip(todo, arrays):
                f.scores = self.forward(dense, idx)
            scores = np.asarray(cur.scores)  # block
            t1 = time.perf_counter()
        with TraceAnnotation("serve.record"):
            self.batcher.answered(n)
            if self.on_batch is not None:
                self.on_batch(batch, scores[:n])
            # batch service time is always REAL seconds (it feeds the
            # deadline admission's EWMA); a virtual clock advances by
            # exactly that duration, so query latencies = virtual queueing
            # delay + real service — deterministic offered load, honest
            # service cost
            service = t1 - t0
            self.batcher.observe_service(service)
            if self._clock_advance is not None:
                self._clock_advance(service)
                done = self.clock()
            else:
                done = t1
            self.stats.batch_latencies_s.append(service)
            for q in batch:
                self.stats.query_latencies_s.append(done - q.arrival_s)
            self.stats.served += n
            self.stats.request_queue_len = len(self.batcher.queue)
            if self.storage is not None:
                self._executed_batches += 1
                if (self.refresh_every_batches
                        and self._executed_batches
                        % self.refresh_every_batches == 0):
                    self._start_refresh()
                self.stats.ps_stats = self.storage.stats()
        return n

    def drain(self, timeout_s: float = 10.0, poll=None) -> None:
        """Serve until the queue empties. Honours the batching window while
        it is open, but force-flushes the partial batch once the head
        query's deadline — or this call's own timeout — is reached, so a
        sub-`max_batch` remainder can never starve (busy-spin bug).
        `poll` substitutes a wrapped poll (the session passes its
        auto-tuner-aware one) so the force-flush law lives only here."""
        poll = self.poll if poll is None else poll
        t0 = time.perf_counter()
        while self.batcher.queue:
            now = self.clock()
            head_deadline = (self.batcher.queue[0].arrival_s
                             + self.batcher.cfg.max_wait_s)
            force = (now >= head_deadline
                     or time.perf_counter() - t0 >= timeout_s)
            served = poll(force=force)
            if (not served and not force
                    and self._clock_advance is not None):
                # a virtual clock only moves when a batch executes, so a
                # partial batch inside its batching window would spin here
                # forever — model the wait by advancing to the deadline
                self._clock_advance(max(0.0, head_deadline - self.clock()))

    def close(self) -> None:
        """Finish any in-flight async refresh — wait for the planner
        (pool shutdown would block on it anyway), install its plan, and
        re-mirror PS stats so the final report sees it — then stop the
        helper thread. Planner exceptions re-raise here, matching the
        poll() path. Does NOT close the parameter server — its prefetch
        worker may outlive this frontend. Idempotent."""
        try:
            if self._refresh_future is not None:
                self._install_pending_refresh()
        finally:
            # a raising planner must not leak the helper pool/thread
            if self._refresh_pool is not None:
                self._refresh_pool.shutdown(wait=True)
                self._refresh_pool = None

    def sla_violations(self) -> int:
        return int(np.sum(np.asarray(self.stats.query_latencies_s)
                          > self.sla_s))
