"""Kernels (kernels/embedding_bag/fused.py): the fused warm-cache
kernel's share of its HBM roofline. Bound: bytes. One launch per table
per batch reads its slot-map, writes its pooled block and reads the
distinct cache-resident rows; those are counted from below as the
distinct rows touched less every row the cold tier gathered in the
window (bench/work.py). Time is the sum of the kernel's device events
over the traced window."""
from bench import work

UNIT = "%"


def is_kernel(name: str) -> bool:
    """The engine's one Pallas call: its op's HLO text in the trace."""
    return "tpu_custom_call" in name


def read(run):
    if run.summary is None or "cold_gathered_rows" not in run.ps_stats:
        return None
    kernel_s = run.summary.seconds_matching(is_kernel)
    if kernel_s <= 0:
        return None
    batches = run.window.batches
    touched = sum(int(run.distinct(b).sum()) for b in batches)
    hits = max(0, touched - int(run.ps_stats["cold_gathered_rows"]))
    launches = len(batches) * run.cfg["num_tables"]
    nbytes = work.fused_bytes(run.cfg, hits, launches, run.batch)
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / kernel_s
