"""Dual-component GEMM kernels (fused and single): the summed roofline time
of their calls, max(int8 ops / peak, bytes / HBM bandwidth) per call, over
their summed device time in the trace, in %."""


def read(ctx):
    t = ctx.trace.kernel_s.get("dual_gemm", 0.0) if ctx.trace else 0.0
    launches = ctx.launch_costs()
    if t <= 0 or not launches:
        return None
    p = ctx.peaks
    ideal = sum(c["linear"].ideal_s(p["int8_ops"], p["hbm_bytes_per_s"]) for c in launches)
    return 100.0 * ideal / t
