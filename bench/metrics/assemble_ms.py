"""Session and engine (serving/server.py `InferenceServer._assemble`):
mean time per batch to assemble the batch's dense and index arrays, read
from the program's `serve.assemble` spans over the window's
`serve.batch` spans (bench/spans.py)."""
from bench import spans

UNIT = "ms"


def read(run):
    return value(spans.of_run(run))


def value(found: list):
    return spans.ms_per_batch(found, "serve.assemble")
