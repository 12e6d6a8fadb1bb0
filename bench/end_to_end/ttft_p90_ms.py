"""90th percentile, over every request due in the window, of the time from
its due time to its first token, in ms. A request with no first token when
the harness stopped waiting counts with that wait (a lower bound)."""

from harness import stats


def read(ctx):
    sent = ctx.driver.sent
    v = stats.percentile(stats.ttft_samples(
        [s.due for s in sent], [s.request.t_first_token for s in sent],
        ctx.t0, ctx.t1, ctx.censor), 90)
    return None if v is None else 1e3 * v
