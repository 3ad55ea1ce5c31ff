"""Arrival processes, one file per kind, found by the ``kind`` a traffic
mix names.  Each defines ``Process(spec, rate, seconds, rng)`` with

- ``n``: how many requests it may release in a window of ``seconds``;
- ``release(now, in_system)``: the due times (seconds from the window's
  start) of the next requests to submit now, in order; ``in_system`` is
  how many submitted requests have not finished, so a closed loop can
  keep its backlog full;
- ``wake(now)``: when to look again if the server has nothing to do.

A request is timed from its due time, not from when it was submitted."""
