"""Batcher (serving/server.py `InferenceServer._hand_out`): the rows the
device computes per served batch, padding included, the mean of the
`padded` statistics of the window's `serve.batch` spans (bench/spans.py).
A full batch reads the configuration's batch; a partial one the batch
shape it was padded to. A program whose `serve.batch` carries no
`padded` reports nothing."""
from bench import spans

UNIT = "rows"


def read(run):
    return value(spans.of_run(run))


def value(found: list):
    batches = spans.named(found, spans.BATCH)
    if not batches or any("padded" not in s.stats for s in batches):
        return None
    return sum(s.stats["padded"] for s in batches) / len(batches)
