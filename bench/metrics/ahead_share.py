"""Session and engine (serving/server.py `InferenceServer.poll`): the
share of the window's serving polls that dispatched the next batch
before waiting for the logits of the batch they answered, the sum of
the `ahead` statistics of the window's `serve.batch` spans over their
number (bench/spans.py). A program whose `serve.batch` carries no
`ahead` reports nothing."""
from bench import spans

UNIT = "%"


def read(run):
    return value(spans.of_run(run))


def value(found: list):
    batches = spans.named(found, spans.BATCH)
    if not batches or any("ahead" not in s.stats for s in batches):
        return None
    return 100.0 * sum(s.stats["ahead"] for s in batches) / len(batches)
