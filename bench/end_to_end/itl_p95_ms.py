"""95th percentile of every gap between consecutive output tokens of a
request with both tokens in the window, pooled over requests, in ms."""

from harness import stats


def read(ctx):
    v = stats.percentile(stats.itl_samples(ctx.driver.token_times(), ctx.t0, ctx.t1), 95)
    return None if v is None else 1e3 * v
