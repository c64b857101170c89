"""Published peaks per chip, keyed by ``jax.devices()[0].device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, 16 GB of
HBM at 819 GB/s.  Roofline shares are of these published peaks, for fp32 work
too.  A device that is not in the table is an error, not a default."""

PEAKS = {
    "TPU v5 lite": {"bf16_flops_s": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit("no published peaks for device kind %r in "
                         "benchmark/lib/peaks.py" % device_kind)
