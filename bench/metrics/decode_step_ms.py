"""Model programs: device time of the macro decode programs per scan
step launched."""
from bench.metrics._common import macro_steps

PROGRAM = "decode_macro_step"


def read(ctx):
    steps, _ = macro_steps(ctx)
    sec, _ = ctx.trace.program_time(PROGRAM)
    if not steps or not sec:
        return None
    return 1e3 * sec / steps
