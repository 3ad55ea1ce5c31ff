"""Scheduler: share of the decode rows in use over the window's macro
steps, from the program's ``serve.macro`` events."""
from bench.metrics._common import macro_steps


def read(ctx):
    steps, active = macro_steps(ctx)
    if not steps:
        return None
    return 100.0 * active / (steps * ctx.serving["max_active"])
