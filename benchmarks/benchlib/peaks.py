"""Published peaks per chip, keyed by the `device_kind` jax reports
(copied from `paddle_tpu/monitor/perf/costmodel.py:TPU_PEAKS`). A device
that is not in the table is an error, never a default."""

# device_kind -> (peak bf16 FLOP/s, peak HBM bytes/s), per chip.
# TPU v5e: Google Cloud documentation, "TPU v5e" system architecture:
# 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s.
TPU_PEAKS = {
    'TPU v5 lite': (197e12, 819e9),
}


def peaks_of(device_kind):
    if device_kind not in TPU_PEAKS:
        raise KeyError(
            'no published peaks recorded for device kind %r: add them, with '
            'their source, to benchmarks/benchlib/peaks.py' % (device_kind,))
    return TPU_PEAKS[device_kind]
