"""Host time of the engine's steps outside waiting for and copying back a
launch's results, per launch: Δ(step_s - device_wait_s - download_s) over
Δlaunches, in ms, from the engine's own step and phase counters
(``engine.step``, ``engine.device_wait``, ``engine.download``)."""

KEYS = ("step_s", "device_wait_s", "download_s")


def read(ctx):
    if any(k not in ctx.stats1 for k in KEYS):
        return None  # the program has no step phase counters
    launches = ctx.delta("decode_steps")
    if launches <= 0:
        return None
    host = ctx.delta("step_s") - ctx.delta("device_wait_s") - ctx.delta("download_s")
    return 1e3 * host / launches
