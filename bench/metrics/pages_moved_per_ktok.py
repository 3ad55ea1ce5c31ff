"""Pool and tiering: logical pages copied host -> HBM per 1000 output
tokens -- tiering promotions (``tier.move`` events) plus demand fetches
before each macro (``fetched`` of ``serve.macro`` events)."""


def read(ctx):
    tokens = sum(1 for r in ctx.log for t in r.times if t <= ctx.window_s)
    if not tokens:
        return None
    moved = sum(e["promoted"] for e in ctx.events if e["type"] == "tier.move")
    moved += sum(e["fetched"] for e in ctx.events
                 if e["type"] == "serve.macro")
    return 1000.0 * moved / tokens
