"""Counting shared by the readers: what the window delivered."""
from __future__ import annotations

from typing import List, Tuple


def prompts_prefilled(ctx) -> List[int]:
    """Prompt lengths of the requests whose first token (the prefill's)
    reached the client inside the window."""
    return [r.prompt_len for r in ctx.log
            if r.times and r.times[0] <= ctx.window_s]


def decode_contexts(ctx) -> List[int]:
    """Keys attended by every decode step that delivered a token inside
    the window: token j >= 1 of a request attends prompt + j keys."""
    out = []
    for r in ctx.log:
        n = sum(1 for t in r.times if t <= ctx.window_s)
        out.extend(r.prompt_len + j for j in range(1, n))
    return out


def macro_steps(ctx) -> Tuple[int, float]:
    """(scan steps launched, sum of active rows x steps) over the
    window's macro launches."""
    ev = [e for e in ctx.events if e["type"] == "serve.macro"]
    return (sum(e["n_steps"] for e in ev),
            sum(e["active"] * e["n_steps"] for e in ev))
