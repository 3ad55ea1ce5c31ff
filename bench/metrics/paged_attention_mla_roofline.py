"""Kernels: the paged MLA kernel's share of its roofline -- the least
time the chip needs for the operations and bytes of the keys each row
attends (the architecture's ``paged_attention_mla_cost``, every layer),
over the ``paged_attention_mla`` op's device time.  Every decode call
sits on the bandwidth side of the ridge (about 2 heads (2 kv_lora + rope)
/ ((kv_lora + rope) itemsize) flop per byte), so the sum of per-call
bounds is the bound of the sums.  A configuration whose architecture has
no such kernel gives nothing."""
from bench import arch, peaks
from bench.metrics._common import decode_contexts

#: the kernel's name in the trace
KERNEL = "paged_attention_mla"


def read(ctx):
    cost = getattr(arch.of(ctx.conf), "paged_attention_mla_cost", None)
    ctxs = decode_contexts(ctx)
    sec = ctx.trace.op_time(KERNEL)
    if cost is None or not ctxs or not sec:
        return None
    c = ctx.conf
    f, b = cost(ctxs, heads=c["num_attention_heads"],
                kv_lora=c["kv_lora_rank"], rope=c["qk_rope_head_dim"],
                kv_itemsize=ctx.pool_itemsize, q_itemsize=ctx.pool_itemsize)
    n = c["num_hidden_layers"]
    pk = peaks.peaks(ctx.device_kind)
    bound = max(n * f / pk["bf16_flops_per_s"], n * b / pk["hbm_bytes_per_s"])
    return 100.0 * bound / sec
