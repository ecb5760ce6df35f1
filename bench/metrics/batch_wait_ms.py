"""Batcher (serving/server.py `Batcher`): the batcher's own count of the
mean wait of a query from its arrival to the pop of its batch, the sum of
the `wait_s_sum` statistics of the window's `serve.batch` spans over the
sum of their `queries` (bench/spans.py). A mean, where `queue_wait_ms`
is a median taken from outside the poll."""
from bench import spans

UNIT = "ms"


def read(run):
    return value(spans.of_run(run))


def value(found: list):
    batches = spans.named(found, spans.BATCH)
    queries = sum(s.stats.get("queries", 0) for s in batches)
    if not queries:
        return None
    return 1e3 * sum(s.stats["wait_s_sum"] for s in batches) / queries
