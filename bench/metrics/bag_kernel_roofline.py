"""Kernels (kernels/embedding_bag/kernel.py): the stacked bag kernel's
share of its HBM roofline. Bound: bytes. Least bytes per batch are the
distinct rows touched per table, the indices and the pooled output
(bench/work.py); time is the sum of the kernel's device events over the
traced window."""
from bench import work

UNIT = "%"


def is_kernel(name: str) -> bool:
    """The engine's one Pallas call: its op's HLO text in the trace."""
    return "tpu_custom_call" in name


def read(run):
    if run.summary is None:
        return None
    kernel_s = run.summary.seconds_matching(is_kernel)
    if kernel_s <= 0:
        return None
    nbytes = sum(work.bag_bytes(run.cfg, run.distinct(b), run.batch)
                 for b in run.window.batches)
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / kernel_s
