"""Ideal compute time of the useful work of the launched steps over their
summed wall time, in %. The linears' integer products count against the
int8 peak; attention and the lm head against bf16. Rows that carry no token
are not work."""


def read(ctx):
    steps = [s for s in ctx.steps() if s.launched]
    launches = ctx.launch_costs()
    if not steps or not launches:
        return None
    p = ctx.peaks
    ideal = sum(c["linear"].ops / p["int8_ops"]
                + (c["attention"].ops + c["head"].ops) / p["bf16_flops"] for c in launches)
    return 100.0 * ideal / sum(s.t1 - s.t0 for s in steps)
