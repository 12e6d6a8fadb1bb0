"""Prompt tokens served from the prefix cache over the prompt tokens of the
requests admitted in the window, in %."""


def read(ctx):
    admitted = [s for s in ctx.driver.sent
                if s.admitted is not None and ctx.t0 <= s.admitted <= ctx.t1]
    tokens = sum(len(s.item.prompt) for s in admitted)
    if tokens == 0:
        return None
    return 100.0 * ctx.delta("prefix_hit_tokens") / tokens
