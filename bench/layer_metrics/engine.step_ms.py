"""Mean wall time of the engine steps that launched, from the harness's
host spans around ``engine.step()``, in ms."""


def read(ctx):
    steps = [s for s in ctx.steps() if s.launched]
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in steps) / len(steps)
