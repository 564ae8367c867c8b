"""Published single-chip peaks, keyed by `jax.devices()[0].device_kind`.
A device that is not in the table is an error, never a default.  (Copy of
`matrixone_tpu/utils/roofline.py` PEAKS; the benchmark keeps its own so
that no later PR can move the yardstick.)"""

PEAKS = {
    "TPU v5 lite": {
        "flops": 197e12,            # bf16 MXU
        "bytes_per_s": 819e9,       # HBM
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device_kind {device_kind!r}: "
                       f"add its row to benchmark/peaks.py PEAKS") from None
