"""Kernels (kernels/embedding_bag/kernel.py): the bag kernel's pace, its
device time over the traced window divided by the row ids it looked up,
the sum of the `lookups` statistics of the window's `serve.put` spans
(padding queries included, as the kernel fetches their rows too). A
program whose `serve.put` carries no `lookups` reports nothing."""
from bench import spans
from bench.metrics.bag_kernel_roofline import is_kernel

UNIT = "ns"


def read(run):
    if run.summary is None:
        return None
    return value(run.summary.seconds_matching(is_kernel),
                 spans.of_run(run))


def value(kernel_s: float, found: list):
    puts = spans.named(found, "serve.put")
    if kernel_s <= 0 or not puts or any("lookups" not in s.stats
                                        for s in puts):
        return None
    return 1e9 * kernel_s / sum(s.stats["lookups"] for s in puts)
