"""Batcher (serving/server.py): median, over the window's queries, of the
time from a query's due time to the start of the poll that serves it."""
import numpy as np

UNIT = "ms"


def read(run):
    waits = np.concatenate([b.start - b.due for b in run.window.batches])
    return float(np.median(waits)) * 1e3
