"""Session and engine (serving/session.py, serving/server.py): mean over
the window's batches of the poll's wall time less the server's own batch
service time (forward plus block) — assembly, staging, bookkeeping."""
import numpy as np

UNIT = "ms"


def read(run):
    return float(np.mean([b.end - b.start - b.service_s
                          for b in run.window.batches])) * 1e3
