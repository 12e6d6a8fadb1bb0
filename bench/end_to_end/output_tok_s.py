"""Output tokens emitted in the window over the window's seconds."""

from harness import stats


def read(ctx):
    return stats.tokens_in(ctx.driver.token_times(), ctx.t0, ctx.t1) / (ctx.t1 - ctx.t0)
