"""Published peaks of the chips the benchmark runs on, keyed by JAX's
`device_kind`. A kind that is not in the table is an error, never a
default: a roofline share against the wrong chip's peak means nothing."""

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,      # bf16 matrix units
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; add them to bench/peaks.py "
                       f"with their source") from None
