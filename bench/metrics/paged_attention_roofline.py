"""Kernels: the paged-attention kernel's share of its roofline -- the
least time the chip needs for the operations and bytes of the keys each
row attends (``bench.flops``), over the kernel's device time.  Every
decode call sits on the bandwidth side of the ridge (intensity about
heads / (2 kv_heads itemsize) flop per byte), so the sum of per-call
bounds is the bound of the sums."""
from bench import flops, peaks
from bench.metrics._common import decode_contexts

#: the kernel's name in the trace
KERNEL = "paged_attention"


def read(ctx):
    ctxs = decode_contexts(ctx)
    sec = ctx.trace.op_time(KERNEL)
    if not ctxs or not sec:
        return None
    c = ctx.conf
    f, b = flops.paged_attention_cost(
        ctxs, heads=c["num_attention_heads"],
        kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], kv_itemsize=ctx.pool_itemsize,
        q_itemsize=ctx.pool_itemsize)
    f, b = f * c["num_hidden_layers"], b * c["num_hidden_layers"]
    pk = peaks.peaks(ctx.device_kind)
    bound = max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
    return 100.0 * bound / sec
