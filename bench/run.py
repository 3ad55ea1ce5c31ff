"""The benchmark's command: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Sets up the cell (weights from the seed, the serving stack, every program
the traffic reaches compiled or loaded from the cache in the checkout),
serves its traffic for ``--seconds``, checks what was served
against the configuration's plain reference, and prints one JSON object
as the last line of standard output: the end-to-end metrics with
``--trace 0``, the per-layer metrics (and the device trace's breakdown)
with ``--trace 1``.  It needs the chips the cell asks for: without them
it exits with code 2 and prints no result.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="keep the profiler trace in this directory")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    from bench import harness
    cell = harness.load(args.workload)
    kw = {}
    if args.keep_trace:
        kw.update(trace_dir=pathlib.Path(args.keep_trace).resolve(),
                  keep_trace=True)
    try:
        out = harness.run(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t_start=T_START, **kw)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
