"""Logits copied to the host per launch: Δlogits_bytes / 1e6 over
Δlaunches, in MB, from the engine's ``logits_bytes`` counter (the bytes of
every logits download at a sync point)."""


def read(ctx):
    if "logits_bytes" not in ctx.stats1:
        return None  # the program has no logits counter
    launches = ctx.delta("decode_steps")
    if launches <= 0:
        return None
    return ctx.delta("logits_bytes") / 1e6 / launches
