"""Cost-model estimator: analytic FLOPs/bytes -> roofline + MFU.

XLA's compiled executables carry their own cost model
(``compiled.cost_analysis()``: flops and bytes accessed of the
optimized program). This module turns that into:

  arithmetic intensity  — flops / bytes accessed;
  roofline bound        — 'compute' when intensity clears the ridge
                          (peak_flops / peak_bandwidth), else
                          'bandwidth';
  ideal_step_s          — max(flops/peak, bytes/bw), the roofline floor;
  mfu_est               — analytic flops / measured step time / peak,
                          given a measured wall time.

This is the repo's ONE table of device peaks (bench.py, bench_extra.py
and chip_smoke.py read it). A TPU is keyed by the ``device_kind`` jax
reports for it, never by the bare platform name — 'tpu' is not one
chip — and a TPU kind that is not in the table raises: a utilization
against another chip's peak is a wrong number, not an estimate. The
``cpu``/``gpu`` rows are nominal comparators for the CPU-side callers
(the sharding tuner's ranking, Model.fit telemetry), not hardware specs.

All jax imports are deferred — the module stays stdlib-importable for
the schema tooling.
"""

__all__ = ['TPU_PEAKS', 'PEAKS', 'platform_peaks', 'cost_of', 'roofline',
           'estimate', 'record']

# jax device_kind -> (peak bf16 FLOP/s, peak HBM bytes/s) per chip
TPU_PEAKS = {
    # TPU v5e. Source: Google Cloud documentation, "TPU v5e" system
    # architecture — 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.
    # Kind string as the chip reports it (chip_smoke.py, 2026-09-26).
    'TPU v5 lite': (197e12, 819e9),
}

# non-TPU platform -> (peak FLOP/s, peak bytes/s): comparators only
PEAKS = {
    'gpu': (312e12, 2039e9),    # A100 bf16 / HBM2e nominal
    'cpu': (1e12, 50e9),        # nominal comparator, not a hardware spec
}


def platform_peaks(platform=None, peak_flops=None, peak_bandwidth=None):
    """(name, peak_flops, peak_bytes_per_s) with overrides applied.

    `platform` is a TPU device_kind or a non-TPU platform name; None
    reads it off ``jax.devices()[0]``. Raises KeyError for a device the
    tables do not know unless both peaks are given."""
    if platform is None:
        import jax
        dev = jax.devices()[0]
        platform = dev.device_kind if dev.platform == 'tpu' \
            else dev.platform
    if peak_flops and peak_bandwidth:
        return platform, float(peak_flops), float(peak_bandwidth)
    table = TPU_PEAKS if platform in TPU_PEAKS else PEAKS
    if platform not in table:
        raise KeyError(
            'no peaks recorded for device %r: add its published peak '
            'FLOP/s and bytes/s, with their source, to '
            'monitor/perf/costmodel.py (known: %s)'
            % (platform, sorted(TPU_PEAKS) + sorted(PEAKS)))
    pf, pb = table[platform]
    return (platform,
            float(peak_flops) if peak_flops else pf,
            float(peak_bandwidth) if peak_bandwidth else pb)


def cost_of(compiled):
    """{'flops', 'bytes_accessed'} from a jax Compiled's cost analysis
    (a dict); None when the program carries no cost."""
    ca = compiled.cost_analysis()
    if not ca:
        return None
    flops = float(ca.get('flops', 0.0) or 0.0)
    nbytes = float(ca.get('bytes accessed', 0.0) or 0.0)
    if flops <= 0.0 and nbytes <= 0.0:
        return None
    return {'flops': flops, 'bytes_accessed': nbytes}


def roofline(flops, bytes_accessed, platform=None, peak_flops=None,
             peak_bandwidth=None):
    """Roofline classification of an analytic (flops, bytes) point."""
    platform, pf, pb = platform_peaks(platform, peak_flops,
                                      peak_bandwidth)
    intensity = (flops / bytes_accessed) if bytes_accessed > 0 \
        else float('inf')
    ridge = pf / pb
    return {
        'platform': platform,
        'peak_flops': pf,
        'peak_bandwidth': pb,
        'arithmetic_intensity': intensity,
        'ridge_intensity': ridge,
        'roofline_bound': 'compute' if intensity >= ridge
        else 'bandwidth',
        'ideal_step_s': max(flops / pf, bytes_accessed / pb),
    }


def estimate(compiled_or_fn, args=None, step_seconds=None, platform=None,
             peak_flops=None, peak_bandwidth=None):
    """Full cost-model estimate of a compiled program.

    Pass a jax Compiled directly, or a callable plus example `args` (it
    is jitted, lowered and compiled here — the persistent compilation
    cache makes the repeat cheap). Returns the cost_of + roofline
    fields, plus 'measured_step_s' / 'mfu_est' / 'roofline_frac' when a
    measured wall time is given; None when no cost model is available.
    """
    compiled = compiled_or_fn
    if args is not None:
        import jax
        compiled = jax.jit(compiled_or_fn).lower(*args).compile()
    cost = cost_of(compiled)
    if cost is None:
        return None
    est = dict(cost)
    est.update(roofline(cost['flops'], cost['bytes_accessed'],
                        platform=platform, peak_flops=peak_flops,
                        peak_bandwidth=peak_bandwidth))
    if step_seconds and step_seconds > 0:
        est['measured_step_s'] = float(step_seconds)
        est['mfu_est'] = cost['flops'] / step_seconds / est['peak_flops']
        ideal = est['ideal_step_s']
        est['roofline_frac'] = (ideal / step_seconds) if ideal else 0.0
    return est


def record(est, registry=None):
    """Publish an estimate onto the perf gauges (mfu_est, arithmetic
    intensity, roofline bound as 0=bandwidth/1=compute) so telemetry
    snapshots carry the cost-model block."""
    from ..registry import default_registry
    from ..telemetry import record_perf_schema
    if not est:
        return None
    reg = registry if registry is not None else default_registry()
    fams = record_perf_schema(reg)
    if 'mfu_est' in est:
        fams['perf_mfu_est'].set(est['mfu_est'])
    intensity = est.get('arithmetic_intensity')
    if intensity is not None and intensity != float('inf'):
        fams['perf_arithmetic_intensity'].set(intensity)
    fams['perf_roofline_bound'].set(
        1.0 if est.get('roofline_bound') == 'compute' else 0.0)
    return reg
