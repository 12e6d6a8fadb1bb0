"""Seconds from process start to the window's opening: weights, engine,
compilation or cache loads, and the traffic's warm period."""


def read(ctx):
    return ctx.setup_s
