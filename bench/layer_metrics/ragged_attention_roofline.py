"""Ragged paged attention kernel: the summed roofline time of its calls,
max(bf16 flops / peak, bytes / HBM bandwidth) per call, over its summed
device time in the trace, in %."""


def read(ctx):
    t = ctx.trace.kernel_s.get("ragged_attention", 0.0) if ctx.trace else 0.0
    launches = ctx.launch_costs()
    if t <= 0 or not launches:
        return None
    p = ctx.peaks
    ideal = sum(c["attention"].ideal_s(p["bf16_flops"], p["hbm_bytes_per_s"]) for c in launches)
    return 100.0 * ideal / t
