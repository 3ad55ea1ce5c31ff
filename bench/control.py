"""Readings that set the correctness limit, on the chip: for each seed,
the harness's check of what the program served (the lower reading) and
of the float8 control put in the program's place on the same requests
(the upper reading; its ``correct`` has to come out false), and the
gap a bfloat16 page pool would read (keys and values rounded).

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

Each seed gets its own weights and traffic and a window of the cell's own
load (the window is shorter than a run's: it only has to finish as many
requests as a run compares).  Prints one JSON line per seed.
"""
import time

import argparse
import gc
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(cell, seed: int, seconds: float, require_chip: bool = True):
    """One seed's readings: the program's check and the control's, each
    with its verdict, and the bfloat16-pool gap."""
    from bench import correct as K
    from bench import harness
    harness.device(require_chip, cell.chips)
    cfg, params, _ = harness.setup(cell, seed)
    w = harness.window(cell, cfg, params, seed=seed, seconds=seconds,
                       rate=float(cell.cell["rate_per_s"]))
    del params
    gc.collect()
    fin = w.finished()
    served = harness.check(cell, seed, fin)
    control = harness.check(cell, seed, fin, control=True)
    kv_bf16, _ = K.control_gap(cell.conf, seed, K.sample(fin, seed),
                               "kv_bf16")
    return {"seed": seed, "requests_finished": len(fin),
            "served": served, "served_correct": harness.verdict(served),
            "control": control, "control_correct": harness.verdict(control),
            "kv_bf16_gap": kv_bf16}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.load(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        try:
            out = readings(cell, seed, args.seconds)
        except harness.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        out["seconds"] = time.monotonic() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
