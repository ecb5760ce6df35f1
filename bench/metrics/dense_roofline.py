"""Dense stages (models/dlrm.py: bottom MLP, interaction, top MLP): their
share of the chip's roofline over the traced window. A batch's least
time is the larger of its dense operations, the step's (from the
configuration's reference module) less the bag additions, over the peak
FLOP/s, and the weights' bytes over the peak HBM bandwidth; the time is
the device's busy time outside the bag kernel, as `dense_ms` takes it.

The peak is the rate the configuration's precision can reach: the
chip's bfloat16 matrix rate (bench/peaks.py) over the bfloat16 passes a
float32 product takes at its `matmul_precision` (`PASSES`), so that the
share can come near 100 % at the stated precision."""
from bench import work
from bench.metrics.bag_kernel_roofline import is_kernel

UNIT = "%"

#: bfloat16 passes of one float32 matrix product on a TPU, by
#: `matmul_precision` (jax.lax.Precision): `default` one, `high` three,
#: `highest` six. A bfloat16 configuration takes one at any precision.
PASSES = {"default": 1, "fastest": 1, "high": 3, "highest": 6}


def flops_peak(cfg: dict, peaks: dict) -> float:
    """The matrix rate the configuration's dtype and precision reach."""
    if cfg.get("dtype", "float32") != "float32":
        return peaks["flops_per_s"]
    precision = cfg.get("matmul_precision", "default")
    if precision not in PASSES:
        raise KeyError(f"no pass count for matmul_precision "
                       f"{precision!r}; add it to PASSES")
    return peaks["flops_per_s"] / PASSES[precision]


def read(run):
    if run.summary is None or not run.window.batches:
        return None
    dense_s = run.summary.busy_s - run.summary.seconds_matching(is_kernel)
    if dense_s <= 0:
        return None
    cfg, b = run.cfg, run.batch
    bag_adds = b * sum(work.table_pooling(cfg)) * cfg["dim"]
    flops = work.step_flops(cfg, b) - bag_adds
    weight_bytes = work.reference(cfg).weight_count(cfg) * work.itemsize(cfg)
    least = max(flops / flops_peak(cfg, run.peaks),
                weight_bytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * len(run.window.batches) / dense_s
