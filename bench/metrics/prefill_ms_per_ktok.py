"""Model programs: device time of the packed-prefill programs per 1000
prompt tokens prefilled (real tokens; the padding is the program's)."""
from bench.metrics._common import prompts_prefilled

PROGRAM = "prefill_batched"


def read(ctx):
    tokens = sum(prompts_prefilled(ctx))
    sec, _ = ctx.trace.program_time(PROGRAM)
    if not tokens or not sec:
        return None
    return 1e6 * sec / tokens
