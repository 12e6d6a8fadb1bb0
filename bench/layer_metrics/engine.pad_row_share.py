"""Share of launched ragged rows that carry no token, from the engine's
counters: 1 - (decode + prefill tokens) / (launches x token budget), in %."""


def read(ctx):
    launches = ctx.delta("decode_steps")
    if launches <= 0:
        return None
    rows = ctx.delta("decode_tokens") + ctx.delta("prefill_tokens")
    return 100.0 * (1.0 - rows / (launches * ctx.engine_conf["token_budget"]))
