"""Device: the whole served step's share of the chip's roofline. Each
batch's least time is the larger of its operations over the peak
FLOP/s and its least bytes over the peak HBM bandwidth (bench/work.py,
from the indices served); their sum over the window's batches is
divided by the window's wall time."""
from bench import work

UNIT = "%"


def read(run):
    cfg, b = run.cfg, run.batch
    least = sum(work.least_seconds(work.step_flops(cfg, b),
                                   work.step_bytes(cfg, run.distinct(x), b),
                                   run.peaks)[0]
                for x in run.window.batches)
    return 100.0 * least / run.window.seconds
