"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports
it.  Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
16 GB of HBM at 819 GB/s).  A device that is not in the table is an
error, not a default."""

PEAKS = {
    'TPU v5 lite': {'bf16_tflops': 197.0, 'hbm_gbs': 819.0,
                    'hbm_gb': 16.0},
    'TPU v5e': {'bf16_tflops': 197.0, 'hbm_gbs': 819.0, 'hbm_gb': 16.0},
}


def peak(device_kind, what):
    """``PEAKS[device_kind][what]``; an unknown device is a
    ``KeyError``."""
    return PEAKS[device_kind][what]
