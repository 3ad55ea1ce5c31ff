"""Scheduler, on the host's clock: 90th percentile, over requests with
two or more tokens in the window, of each request's longest gap between
deliveries -- a macro of the tuned period, plus any joiners' prefill at
its boundary.  Bimodal from seed to seed (whether the tail's requests
met a prefill), so it is reported here and bounds nothing."""
from bench import clientmetrics as CM


def read(ctx):
    return CM.stall_ms(ctx.log, ctx.window_s, 0.9)
