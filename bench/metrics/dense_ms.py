"""Dense stages (models/dlrm.py): device busy time per batch outside the
bag kernel — MLPs, interaction, index layout, over the traced window."""
from bench.metrics.bag_kernel_roofline import is_kernel

UNIT = "ms"


def read(run):
    if run.summary is None or not run.window.batches:
        return None
    kernel_s = run.summary.seconds_matching(is_kernel)
    return 1e3 * (run.summary.busy_s - kernel_s) / len(run.window.batches)
