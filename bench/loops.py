"""The measured window: queries through `ServingSession.submit` and
`poll` on the real clock, as a closed loop that keeps full batches
queued or as an open loop of arrivals due at fixed times.

Every query is made in set-up; the loops only hand them to the session.
Each poll that serves a batch is recorded with its host-clock start and
end, the batch service time the server measured, and the query ids and
logits the batch returned.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np
from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class Batch:
    start: float               # host clock when the serving poll began
    end: float                 # ... and when it returned the answers
    service_s: float           # the server's own forward + block time
    qids: np.ndarray
    due: np.ndarray            # each query's due time (host clock)
    logits: np.ndarray


@dataclasses.dataclass
class Window:
    start: float
    end: float
    batches: list              # Batch served inside the window
    attempted: int             # queries submitted
    lateness_s: np.ndarray     # open loop: submit time - due time
    drained: list = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def served_in_window(self) -> int:
        return sum(len(b.qids) for b in self.batches)

    def served(self) -> int:
        return sum(len(b.qids) for b in self.batches + self.drained)

    def latencies_s(self) -> np.ndarray:
        """Due time to answer, for every query answered in the window."""
        return np.concatenate([b.end - b.due for b in self.batches])


class Server:
    """Wraps the session's poll with the window's records."""

    def __init__(self, session):
        self.session = session
        self.queue = session.server.batcher.queue
        self._last = None
        session.server.on_batch = self._on_batch

    def _on_batch(self, batch, scores) -> None:
        self._last = (np.fromiter((q.qid for q in batch), np.int64,
                                  len(batch)),
                      np.fromiter((q.arrival_s for q in batch), np.float64,
                                  len(batch)),
                      np.array(scores, np.float32))

    def poll(self, force: bool = False) -> Batch | None:
        t0 = time.perf_counter()
        with TraceAnnotation("bench.poll"):
            n = self.session.poll(force=force)
        t1 = time.perf_counter()
        if not n:
            return None
        qids, due, logits = self._last
        return Batch(t0, t1, self.session.stats.batch_latencies_s[-1], qids,
                     due, logits)

    def drain(self) -> list:
        """Answer whatever is still queued; the batches served."""
        out = []
        while self.queue:
            b = self.poll(force=True)
            if b is not None:
                out.append(b)
        return out


def closed_loop(server: Server, queries: list, seconds: float,
                queued: int) -> Window:
    """Keep at least `queued` queries waiting, and serve, until the first
    batch that ends past `seconds`; the window ends with that batch.
    Each query is due when it is submitted; those still queued at the
    end are left for `Server.drain`."""
    n, nxt, batches = len(queries), 0, []
    start = time.perf_counter()
    stop = start + seconds
    while True:
        with TraceAnnotation("bench.submit"):
            while len(server.queue) < queued:
                q = queries[nxt % n]
                q.arrival_s = None
                server.session.submit(q)
                nxt += 1
        b = server.poll()
        if b is not None:
            batches.append(b)
            if b.end >= stop:
                break
    return Window(start, batches[-1].end, batches, nxt, np.zeros(0))


def open_loop(server: Server, queries: list, due_s: np.ndarray,
              max_wait_s: float) -> Window:
    """Submit each query once its due time has passed and serve until
    every query is answered; the window ends with the last answer."""
    n, nxt, batches = len(queries), 0, []
    late = np.empty(n)
    start = time.perf_counter()
    while nxt < n or server.queue:
        now = time.perf_counter()
        if nxt < n and start + due_s[nxt] <= now:
            with TraceAnnotation("bench.submit"):
                while nxt < n and start + due_s[nxt] <= now:
                    q = queries[nxt]
                    q.arrival_s = start + due_s[nxt]
                    server.session.submit(q)
                    late[nxt] = now - q.arrival_s
                    nxt += 1
        b = server.poll()
        if b is not None:
            batches.append(b)
            continue
        wake = []
        if nxt < n:
            wake.append(start + due_s[nxt])
        if server.queue:
            wake.append(server.queue[0].arrival_s + max_wait_s)
        pause = min(wake) - time.perf_counter()
        if pause > 0:
            time.sleep(pause)
    return Window(start, batches[-1].end, batches, n, late)
