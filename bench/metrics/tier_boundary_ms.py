"""Pool and tiering, timed by the program: mean milliseconds, per macro
boundary in the window, of the program's ``serve.monitor`` span (the
``obs.span`` events of that name): the mass merge, tiering and the
tuner's step -- Cori's own host cost per movement period.  A program
that opens no such span gives nothing."""
import statistics

SPAN = "serve.monitor"


def read(ctx):
    ms = [e["ms"] for e in ctx.events
          if e["type"] == "obs.span" and e["name"] == SPAN]
    return statistics.fmean(ms) if ms else None
