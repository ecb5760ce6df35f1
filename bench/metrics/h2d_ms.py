"""Session and engine (serving/session.py, the device-resident engine):
mean time per batch of the batch's host-to-device copy, dense features
and indices, over the window's `serve.batch` spans (bench/spans.py). The
program's `serve.put` span issues the copy, dispatches the engine and
ends when the copy has landed; its `bytes` statistic holds what was
put."""
from bench import spans

UNIT = "ms"


def read(run):
    return value(spans.of_run(run))


def value(found: list):
    return spans.ms_per_batch(found, "serve.put")
