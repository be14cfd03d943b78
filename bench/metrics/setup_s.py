"""Seconds from process start to the window: generation, tuning,
materialization, compilation or cache loads, and the warm-up."""


def read(ctx):
    return ctx.setup_s
