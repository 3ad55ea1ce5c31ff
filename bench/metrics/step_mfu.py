"""Device: model FLOPs of every token the window processed (prefilled
prompts and decoded tokens, ``bench.flops.model_flops``) over the
window's seconds times the chip's peak bf16 FLOP/s."""
from bench import flops, peaks
from bench.metrics._common import decode_contexts, prompts_prefilled


def read(ctx):
    f = flops.model_flops(ctx.conf, prompts_prefilled(ctx),
                          decode_contexts(ctx))
    if not f:
        return None
    pk = peaks.peaks(ctx.device_kind)
    return 100.0 * f / (ctx.window_s * pk["bf16_flops_per_s"])
