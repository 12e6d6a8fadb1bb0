"""Host sampling per launch: Δsample_s over Δlaunches, in ms, from the
engine's ``engine.sample`` phase counter (argmax or top-k over each decoding
slot's logits, token emission, evictions)."""


def read(ctx):
    if "sample_s" not in ctx.stats1:
        return None  # the program has no step phase counters
    launches = ctx.delta("decode_steps")
    if launches <= 0:
        return None
    return 1e3 * ctx.delta("sample_s") / launches
