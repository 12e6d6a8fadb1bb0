"""Device-to-host copy of results per launch, after the device is done:
Δdownload_s over Δlaunches, in ms, from the engine's ``engine.download``
phase counter."""


def read(ctx):
    if "download_s" not in ctx.stats1:
        return None  # the program has no step phase counters
    launches = ctx.delta("decode_steps")
    if launches <= 0:
        return None
    return 1e3 * ctx.delta("download_s") / launches
