"""TPU v5e hardware constants for the roofline model (per instructions)."""

PEAK_FLOPS_BF16 = 197e12       # FLOP/s per chip
HBM_BW = 819e9                 # B/s per chip
ICI_BW_PER_LINK = 50e9         # B/s per link
VMEM_BYTES = 128 * 2**20

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1,
    "pred": 1, "c64": 8, "c128": 16,
}
