"""End to end: 90th percentile of due time to first token over every
request due in the window (one still waiting enters with its wait to
the window's end)."""
from bench import clientmetrics as CM


def read(ctx):
    return CM.ttft_ms(ctx.log, ctx.window_s, 0.9)
