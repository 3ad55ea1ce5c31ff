"""End to end: 90th percentile, over requests finished in the window, of
the time per output token after the first."""
from bench import clientmetrics as CM


def read(ctx):
    return CM.tpot_ms(ctx.log, ctx.window_s, 0.9)
