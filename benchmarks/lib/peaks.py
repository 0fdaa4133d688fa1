"""Published peaks per ``device_kind``. A device that is not in the
table is an error, not a default."""

#: HBM bandwidth in GB/s. Source: Google Cloud documentation, "TPU v5e":
#: 16 GB of HBM2e at 819 GB/s per chip.
PEAK_HBM_GBPS = {
    "TPU v5 lite": 819.0,
}


def peak_hbm_gbps(device_kind: str) -> float:
    if device_kind not in PEAK_HBM_GBPS:
        raise KeyError(
            f"no published HBM peak for device_kind {device_kind!r}: add it "
            "to benchmarks/lib/peaks.py with its source")
    return PEAK_HBM_GBPS[device_kind]
