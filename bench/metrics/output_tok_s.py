"""End to end: every token delivered in the window over its seconds."""
from bench import clientmetrics as CM


def read(ctx):
    return CM.output_tok_s(ctx.log, ctx.window_s)
