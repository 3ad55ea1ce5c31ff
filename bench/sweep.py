"""Find the highest rate a cell's configuration sustains: one set-up,
then one window per offered rate, on the chip.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 1,2,3

Prints one JSON line per rate: requests due and finished, output tokens/s,
time-to-first-token quantiles, and the backlog (requests due but not yet
started) at the window's middle and end.  A backlog that grows from the
middle to the end marks a rate above the knee.  The cell's own rate is
set from this by hand, at about 0.8 of the knee, and recorded in PERF.md.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def backlog(log, at_s: float) -> int:
    return sum(1 for r in log if r.due_s <= at_s
               and not (r.times and r.times[0] <= at_s))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import numpy as np

    from bench import clientmetrics as CM
    from bench import harness
    cell = harness.load(args.workload)
    try:
        harness.device(True, cell.chips)
    except harness.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    cfg, params, _ = harness.setup(cell, args.seed)
    print(json.dumps({"setup_s": time.monotonic() - T_START}), flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        w = harness.window(cell, cfg, params, seed=args.seed,
                           seconds=args.seconds, rate=rate)
        log, ws = w.deliveries(), w.window_s
        first = [(r.times[0] if r.times and r.times[0] <= ws else ws)
                 - r.due_s for r in log if r.due_s < ws]
        occ = [e["active"] for e in w.events if e["type"] == "serve.macro"]
        print(json.dumps({
            "rate": rate, "due": len(w.due()), "finished": len(w.finished()),
            "output_tok_s": CM.output_tok_s(log, ws),
            "ttft_p50_ms": float(np.quantile(first, 0.5)) * 1e3,
            "ttft_p90_ms": float(np.quantile(first, 0.9)) * 1e3,
            "backlog_mid": backlog(log, ws / 2),
            "backlog_end": backlog(log, ws),
            "mean_active_rows": float(np.mean(occ)) if occ else 0.0,
            "period": w.period,
            "slowest_steps_s": [st["s"] for st in w.slow_steps]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
