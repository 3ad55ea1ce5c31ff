"""Quickstart: the paper's pipeline end-to-end in one page.

Generates the backprop trace, collects the reuse histogram, computes the
dominant reuse (Eq. 1), builds the candidate ladder (Eq. 2), tunes the
page-scheduling period against the hybrid-memory simulator, and compares
against the fixed frequencies of prior systems (Table I).

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro import compile_cache
from repro.core import (bin_trace, candidate_periods, dominant_reuse,
                        generate, optimal_runtime, prune_insignificant,
                        reuse_distance_histogram, run_cori, table_i_runtimes)


def main():
    compile_cache.enable()
    # 1. Reuse Collector: one profiling run
    trace = generate("backprop")
    bins = bin_trace(trace)
    hist = prune_insignificant(
        reuse_distance_histogram(trace.pages, bin_width=1000))
    print(f"trace: {trace.name}, {trace.num_accesses:,} accesses over "
          f"{trace.num_pages:,} pages")
    print("reuse histogram:",
          {int(v): int(c) for v, c in zip(hist.values, hist.counts)})

    # 2. Frequency Generator: Eq. 1 + Eq. 2
    dr = dominant_reuse(hist)
    ladder = candidate_periods(dr, trace.num_accesses)
    print(f"dominant reuse DR = {dr:,.0f} requests")
    print(f"candidate periods: {[int(p) for p in ladder[:6]]} ...")

    # 3. Tuner: trial candidates against the system (simulator here)
    for sched in ("reactive", "predictive"):
        crun = run_cori(bins, trace, sched)
        opt = optimal_runtime(bins, sched)
        slack = crun.result.best_runtime_tried / opt["runtime"] - 1
        print(f"\n[{sched}] Cori chose period {crun.chosen_period:,.0f} in "
              f"{crun.trials} trials -> {slack:.1%} from optimal "
              f"(optimal period {opt['period']:,.0f})")
        t1 = table_i_runtimes(bins, sched)
        for name, r in sorted(t1.items(), key=lambda kv: kv[1].runtime):
            gap = r.runtime / opt["runtime"] - 1
            print(f"    {name:10s} period={r.period_requests:7d}  "
                  f"gap={gap:7.1%}")


if __name__ == "__main__":
    main()
