"""End to end: process start to the window's start (import, weights,
warm-up, compiles or cache loads)."""


def read(ctx):
    return ctx.setup_s
