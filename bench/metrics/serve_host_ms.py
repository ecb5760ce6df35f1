"""Session and engine (serving/server.py `InferenceServer.poll`): the
program's own count of a batch's host time, the mean over the window's
`serve.batch` spans of the span less its `serve.forward` (the batch
service time): assembly, staging and bookkeeping (bench/spans.py).
`host_ms_per_batch` times the same from outside the poll."""
from bench import spans

UNIT = "ms"


def read(run):
    return value(spans.of_run(run))


def value(found: list):
    whole = spans.ms_per_batch(found, spans.BATCH)
    forward = spans.ms_per_batch(found, "serve.forward")
    if whole is None or forward is None:
        return None
    return whole - forward
