"""Kernels: the expert layer's grouped matmul's share of its roofline --
the least time the chip needs for the token-expert pairs routed to held
experts and the weights of the experts they touch (the architecture's
``expert_gmm_cost``, from the ``moe_pairs_held`` / ``moe_experts_touched``
counts of the program's ``serve.macro`` and ``serve.admit`` events), over
the ``gmm`` op's device time.  A program without those counts, or a
configuration without held experts, gives nothing."""
from bench import arch, peaks

#: the grouped matmul's name in the trace
KERNEL = "gmm"


def read(ctx):
    cost = getattr(arch.of(ctx.conf), "expert_gmm_cost", None)
    ev = [e for e in ctx.events
          if e["type"] in ("serve.macro", "serve.admit")]
    pairs = sum(e.get("moe_pairs_held", 0) for e in ev)
    touched = sum(e.get("moe_experts_touched", 0) for e in ev)
    sec = ctx.trace.op_time(KERNEL)
    if cost is None or not pairs or not sec:
        return None
    c = ctx.conf
    f, b = cost(pairs, touched, hidden=c["hidden_size"],
                expert_width=c["moe_intermediate_size"])
    pk = peaks.peaks(ctx.device_kind)
    return 100.0 * max(f / pk["bf16_flops_per_s"],
                       b / pk["hbm_bytes_per_s"]) / sec
