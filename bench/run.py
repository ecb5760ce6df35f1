#!/usr/bin/env python3
"""Runs one benchmark cell once on the chip this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration file and its traffic file are found by name
from `BENCHMARK.json`; each metric is read by `bench/metrics/<name>.py`.
A run is set-up (weights and traffic made from the seed, the storage
built, every shape the cell uses compiled or loaded from the persistent
compile cache), the measured window of `--seconds` through
`ServingSession.submit`/`poll`, and then the check: every answer served
is compared with the plain reference of the configuration.

With `--trace 0` the last line of standard output holds the cell's
end-to-end metrics; with `--trace 1` the window runs under the profiler
and the line holds the per-layer metrics, the device's busy time and
`breakdown`. Earlier lines, on standard error, say what was measured;
the last of them give each compared number beside its limit.

Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import generator, loops, work  # noqa: E402
from bench.peaks import peaks_for  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench", "trace")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


class Spec:
    """BENCHMARK.json and the files it names, for one cell."""

    def __init__(self, workload: str):
        bench = load_json("BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; "
                             f"BENCHMARK.json has {sorted(cells)}")
        self.cell = cells[workload]
        configs = {c["name"]: c for c in bench["configs"]}
        self.config = load_json(configs[self.cell["config"]]["file"])
        self.traffic = load_json(os.path.join(
            "bench", "traffic", self.cell["traffic"] + ".json"))

        def mine(metrics):
            return [m["name"] for m in metrics
                    if workload in m.get("workloads", [workload])]
        self.end_to_end = mine(bench["end_to_end"])
        self.per_layer = mine(bench["per_layer"])


def require_chips(n: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU: JAX's first device is {devices[0].platform!r}; "
            f"the benchmark measures only on a TPU")
        raise SystemExit(2)
    if len(devices) < n:
        log(f"the cell asks for {n} chips, JAX finds {len(devices)}")
        raise SystemExit(2)
    return devices[:n]


class CompileMonitor:
    """Times of the backend compilations this process makes."""

    def __init__(self):
        self.times: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.times.append((time.perf_counter(), duration))

    def between(self, lo: float, hi: float) -> tuple:
        inside = [d for t, d in self.times if lo <= t <= hi]
        return len(inside), sum(inside)


class Run:
    """What the metric readers read: the window's records, the storage's
    counters over the window, the trace summary, and the work of each
    served batch."""

    def __init__(self, cfg, setup_s, window, ps_stats, indices,
                 summary=None, device_kind=None):
        self.cfg = cfg
        self.setup_s = setup_s
        self.window = window
        self.ps_stats = ps_stats
        self.summary = summary
        self.device_kind = device_kind
        self._indices = indices
        self._distinct: dict = {}

    @property
    def peaks(self) -> dict:
        return peaks_for(self.device_kind)

    @property
    def batch(self) -> int:
        return self.cfg["batch"]

    def distinct(self, b: loops.Batch) -> np.ndarray:
        """[T] distinct rows the batch touched per table (padding rows,
        row 0 of each table, included)."""
        key = id(b)
        if key not in self._distinct:
            idx = self._indices[b.qids % len(self._indices)]
            if len(idx) < self.batch:
                pad = np.zeros((self.batch - len(idx),) + idx.shape[1:],
                               idx.dtype)
                idx = np.concatenate([idx, pad])
            self._distinct[key] = work.distinct_rows(
                idx, self.cfg["rows"], self.cfg["pooling"])
        return self._distinct[key]


def load_reader(name: str):
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def gap(served: np.ndarray, ref: np.ndarray) -> float:
    """Largest |served - reference| over the reference's RMS."""
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    if not np.all(np.isfinite(served)):
        return math.inf
    rms = float(np.sqrt(np.mean(ref ** 2)))
    return float(np.max(np.abs(served - ref))) / rms


def check(cfg: dict, seed: int, window: loops.Window, indices, dense,
          pooled: list) -> dict:
    """Each compared number with its limit. Every answer served, in the
    window and after it, against the reference's; pooled rows too where
    the storage hands them to the engine."""
    ref_mod = work.reference(cfg)
    batches = window.batches + window.drained
    qids = np.concatenate([b.qids for b in batches])
    uniq, inv = np.unique(qids, return_inverse=True)
    ref_logits, ref_pooled = ref_mod.reference(
        seed, cfg, indices, dense, uniq % len(indices),
        want_pooled=bool(pooled))
    served = np.concatenate([b.logits for b in batches])
    numbers = {"logit_gap": gap(served, ref_logits[inv])}
    if pooled:
        got = np.concatenate([np.asarray(p)[:len(b.qids)]
                              for p, b in zip(pooled, batches)])
        numbers["pooled_gap"] = gap(got, ref_pooled[inv])
    limits = cfg["correct"]
    return {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}


def free(tree) -> None:
    """Delete every device array of `tree` not deleted yet."""
    for leaf in jax.tree.leaves(tree):
        if not leaf.is_deleted():
            leaf.delete()


def make_queries(traffic: generator.Traffic) -> list:
    from repro.serving import Query
    return [Query(qid=i, dense=traffic.dense[i], indices=traffic.indices[i])
            for i in range(len(traffic))]


def run_cell(cfg: dict, traffic: dict, seed: int, seconds: float,
             trace: bool, metrics: list, t_start: float,
             monitor: CompileMonitor | None = None, fault=None) -> dict:
    """One run of a cell; returns the result object. `fault`, for the
    harness's own tests, breaks the engine under the window."""
    from bench.deploy import Deployment
    ref_mod = work.reference(cfg)
    dev = jax.devices()[0]
    batch = cfg["batch"]
    warm = cfg["prewarm_batches"]

    params = jax.block_until_ready(ref_mod.init_params(seed, cfg))
    if traffic["loop"] == "closed":
        due_s = None
        window_batches = traffic["pool_batches"]
    else:
        due_s = generator.open_loop_due(traffic, seconds, seed)
        window_batches = -(-len(due_s) // batch)
    with jax.profiler.TraceAnnotation("bench.generate"):
        made = generator.make_traffic(cfg, traffic, seed,
                                      warm + window_batches, batch)
    n_win = len(due_s) if due_s is not None else window_batches * batch
    w_idx = made.indices[warm * batch:warm * batch + n_win]
    w_dense = made.dense[warm * batch:warm * batch + n_win]
    warm_idx = made.indices[:warm * batch]
    rows = work.table_rows(cfg)
    uniq = work.distinct_rows(w_idx[:batch], rows,
                              cfg["pooling"]) * 100.0 / np.asarray(rows)
    log("unique_access_pct_per_table (first window batch) "
        + " ".join(f"{u:.3f}" for u in uniq))

    dep = Deployment(cfg, params, trace=warm_idx if warm else None)
    if not dep.storage.capabilities().device_resident:
        free(params["embedding"])
    server = loops.Server(dep.session)
    if fault is not None:
        fault(dep.session)
    for k in range(warm):
        dep.session.submit_batch(made.dense[k * batch:(k + 1) * batch],
                                 warm_idx[k * batch:(k + 1) * batch],
                                 qid0=n_win + k * batch)
        server.drain()
    dep.storage.reset_stats()
    dep.pooled.clear()
    queries = make_queries(generator.Traffic(w_idx, w_dense))
    # what set-up made lives through the window: keep the collector off it
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    if due_s is None:
        window = loops.closed_loop(server, queries, seconds,
                                   traffic["queued_batches"] * batch)
    else:
        window = loops.open_loop(server, queries, due_s,
                                 cfg["max_wait_ms"] / 1e3)
    if trace:
        jax.profiler.stop_trace()
    ps_stats = dep.storage.stats()
    window.drained = server.drain()
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))

    log(f"setup_s={setup_s!r} window_s={window.seconds!r} "
        f"batches={len(window.batches)} "
        f"served_in_window={window.served_in_window()} "
        f"attempted={window.attempted} served={window.served()}")
    if len(window.lateness_s):
        late = window.lateness_s * 1e3
        log(f"generator lateness_ms p50={float(np.percentile(late, 50))!r} "
            f"p99={float(np.percentile(late, 99))!r} "
            f"max={float(late.max())!r} "
            f"(queries submitted between polls; latency counts from "
            f"the due time)")
    if monitor is not None:
        n, s = monitor.between(window.start, window.end)
        log(f"compiles_in_window={n} compile_s_in_window={s!r}")

    summary = None
    if trace:
        from bench import trace_reduce
        path = trace_reduce.find_xplane(TRACE_DIR)
        summary = trace_reduce.summarize(trace_reduce.load(path))
        log(f"trace {os.path.relpath(path, ROOT)} "
            f"window_s={summary.window_s!r} busy_s={summary.busy_s!r}")

    pooled = list(dep.pooled)
    dep.close()
    free(params["embedding"])
    del dep, server, params
    gc.unfreeze()
    gc.collect()
    run = Run(cfg, setup_s, window, ps_stats, w_idx, summary,
              dev.device_kind)
    values = {}
    units = {}
    for m in metrics:
        reader = load_reader(m)
        v = reader.read(run)
        if v is not None:
            values[m] = float(v)
            units[m] = reader.UNIT
    compared = check(cfg, seed, window, w_idx, w_dense, pooled)
    failed = window.attempted - window.served()
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in compared.values())
    result = {
        "correct": bool(correct),
        "attempted": int(window.attempted),
        "failed": int(failed),
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in values.items()},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": 1, "memory_peak_bytes": memory_peak},
    }
    if summary is not None:
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_ops(),
                               "idle_gaps": summary.top_gaps()}
    result["check"] = compared
    for k, c in compared.items():
        log(f"check {k}={c['value']!r} limit={c['limit']!r}")
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = Spec(args.workload)
    devices = require_chips(spec.cell["chips"])
    from repro.utils import enable_compile_cache
    cache = enable_compile_cache()
    monitor = CompileMonitor()
    log(f"device platform={devices[0].platform} "
        f"kind={devices[0].device_kind} count={len(devices)} "
        f"compile_cache={os.path.relpath(cache, ROOT)} "
        f"workload={args.workload} seed={args.seed}")
    metrics = spec.per_layer if args.trace else spec.end_to_end
    result = run_cell(spec.config, spec.traffic, args.seed,
                      args.seconds, bool(args.trace), metrics, T_START,
                      monitor)
    result["device"]["count"] = len(devices)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
