"""Median latency, due time to answer, over every query of the window."""
import numpy as np

UNIT = "ms"


def read(run):
    return float(np.percentile(run.window.latencies_s(), 50)) * 1e3
