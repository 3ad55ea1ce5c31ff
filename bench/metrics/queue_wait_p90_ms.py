"""Scheduler, timed by the program: 90th percentile, over the requests
admitted in the window, of each one's queue wait -- submit to the start
of its admission -- from the ``wait_ms`` of the program's ``serve.admit``
events.  A program whose events carry no ``wait_ms`` gives nothing."""
from bench import clientmetrics as CM


def read(ctx):
    waits = [w for e in ctx.events if e["type"] == "serve.admit"
             for w in e.get("wait_ms", ())]
    if not waits:
        return None
    return CM.tail(waits, 0.9)
