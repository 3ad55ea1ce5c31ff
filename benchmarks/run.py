"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (derived = the figure's headline
number).  Results are also written as JSON under ``benchmarks/out/`` for
EXPERIMENTS.md.

    PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile

from benchmarks.common import Timer
from repro import compile_cache


def smoke() -> None:
    """Fast bit-rot check (CI): tiny-shape runs of the benchmarks wired to
    the serving/tuning path -- online, sweep and traffic -- asserting each
    one's headline invariant still holds.  Results go to a temp dir
    (``REPRO_BENCH_OUT``) so the smoke can never diff against -- or
    clobber -- locally generated results under benchmarks/out/."""
    if "REPRO_BENCH_OUT" not in os.environ:
        os.environ["REPRO_BENCH_OUT"] = tempfile.mkdtemp(
            prefix="repro-bench-smoke-")
    print(f"# results -> {os.environ['REPRO_BENCH_OUT']}", file=sys.stderr)
    print("name,us_per_call,derived")

    from benchmarks import online
    with Timer() as t:
        on = online.run(quick=True)
    print(f"smoke_online,{t.us:.0f},"
          f"vs_best_fixed_steady={on['online_vs_best_fixed_steady']:.3f}")
    assert on["online"]["time_to_converge_steps"] is not None, \
        "online tuner never converged"

    from benchmarks import sweep
    with Timer() as t:
        sw = sweep.run(quick=True)
    err = max(v["max_rel_err"] for v in sw.values())
    print(f"smoke_sweep,{t.us:.0f},max_rel_err={err:.1e}")
    assert err < 1e-6, "batched sweep diverged from the loop oracle"

    from benchmarks import traffic
    with Timer() as t:
        tr = traffic.run(quick=True)
    print(f"smoke_traffic,{t.us:.0f},"
          f"vs_best_fixed_steady={tr['online_vs_best_fixed_steady']:.3f};"
          f"token_identical={tr['token_parity']['token_identical']};"
          f"mem_reduction={tr['cache_memory']['reduction']:.2f}")
    assert tr["token_parity"]["token_identical"], \
        "fully-paged decode diverged from per-request generate"
    assert tr["requests"]["completed"] > 0, "no traffic completed"
    assert tr["cache_memory"]["reduction"] >= 0.25, \
        "bucketed paged rows must cut peak cache memory by >= 25% vs the " \
        f"dense max_len provisioning (got {tr['cache_memory']['reduction']:.1%})"

    # hostile traffic: the hardened tuner must ride out flash crowds,
    # correlated bursts and diurnal swings within 1.15x of the best fixed
    # period in EVERY phase, and a poisoned TRIAL sweep must revert to
    # the last attested period (results land in BENCH_hostile.json)
    with Timer() as t:
        ho = traffic.hostile(quick=True)
    pt = ho["poisoned_trial"]
    print(f"smoke_hostile,{t.us:.0f},max_regret={ho['max_regret']:.3f};"
          f"guard_reverted={pt['reverted']};"
          f"tune_cycles={ho['tuner']['tune_cycles']}")
    assert ho["max_regret"] <= 1.15, \
        "hostile traffic shook the tuner: per-phase regret must stay " \
        f"<= 1.15x best fixed (got {ho['max_regret']:.3f}x)"
    assert pt["reverted"], \
        "poisoned TRIAL sweep must abort and revert to the last " \
        f"attested period (got {pt})"

    # the flight recorder must have captured the hostile run: a JSONL
    # event log with the full tuner decision timeline, replayable by
    # ``python -m repro.obs.report`` (uploaded as a CI artifact)
    from repro import obs
    from repro.obs import report as obs_report
    assert ho["metrics"]["schema"] == obs.SCHEMA, \
        f"benchmark metrics schema drifted: {ho['metrics'].get('schema')}"
    events = obs.read_jsonl(ho["events_jsonl"])
    transitions = [e for e in events if e["type"] == "tuner.transition"]
    assert transitions, "hostile event log carries no tuner transitions"
    trace = obs_report.decision_trace(events)
    assert any("->" in ln for ln in trace), \
        "decision trace failed to reconstruct the tuner timeline"
    print(f"smoke_obs,0,events={len(events) - 1};"
          f"transitions={len(transitions)};trace_lines={len(trace)}")

    # serving throughput: the macro-step hot loop must not regress below
    # the per-token paged path, with the four-way bit-parity bar intact
    # (results land in BENCH_serving.json for cross-PR tracking)
    with Timer() as t:
        sp = traffic.serving_perf(quick=True)
    print(f"smoke_serving,{t.us:.0f},"
          f"macro_speedup={sp['speedup_macro_vs_per_token']:.2f}x;"
          f"macro_tok_s={sp['modes']['macro']['tokens_per_sec']:.0f};"
          f"parity={sp['token_identical_all_modes']}")
    assert sp["token_identical_all_modes"], \
        "macro/paged/dense decode diverged from per-request generate"
    assert (sp["modes"]["macro"]["tokens_per_sec"]
            >= sp["modes"]["paged"]["tokens_per_sec"]), \
        "macro-step decode must be at least as fast as the per-token " \
        f"paged path (got {sp['speedup_macro_vs_per_token']:.2f}x)"
    # the overlap and telemetry wall-clock bars bind where overlap (and
    # a clean paired measurement) is physically possible -- >= 2 cores.
    # A single-core host time-slices the scan, the boundary work and the
    # recorder on one core, so both floors widen to no-material-
    # regression (see benchmarks/traffic.py and docs/serving.md)
    multicore = sp["overlap_parallel_substrate"]
    ov_floor = 1.0 if multicore else 0.90
    print(f"smoke_overlap,0,"
          f"speedup={sp['speedup_overlap_vs_sync']:.3f};"
          f"pipelined_parity={sp['parity_vs_generate']['pipelined']}")
    assert sp["parity_vs_generate"]["pipelined"], \
        "the pipelined loop diverged from per-request generate"
    assert sp["speedup_overlap_vs_sync"] >= ov_floor, \
        "the pipelined loop must not serve slower than the synchronous " \
        f"macro loop (got {sp['speedup_overlap_vs_sync']:.2f}x, " \
        f"floor {ov_floor:.2f}x)"
    ov = sp["telemetry_overhead"]
    oh_floor = 0.97 if multicore else 0.90
    print(f"smoke_telemetry,0,overhead_ratio={ov['ratio']:.3f};"
          f"enabled_tok_s={ov['enabled_tok_s']:.0f}")
    assert ov["ratio"] >= oh_floor, \
        "telemetry-enabled macro-loop throughput regressed vs disabled " \
        f"(got {ov['ratio']:.3f}, floor {oh_floor:.2f})"

    # paged MLA admission: compressed-row deepseek pages out of the same
    # slot pool, token-identical and >= 1.5x leaner than dense rows
    # (results land in traffic_mla.json for cross-PR tracking)
    with Timer() as t:
        m = traffic.mla(quick=True)
    print(f"smoke_mla,{t.us:.0f},"
          f"page_reduction={m['page_reduction_x']:.2f}x;"
          f"parity={m['token_identical']}")
    assert m["token_identical"], \
        "paged MLA decode diverged from per-request generate"
    assert m["page_reduction_x"] >= 1.5, \
        "paged MLA admission must provision >= 1.5x fewer pages than " \
        f"dense rows (got {m['page_reduction_x']:.2f}x)"

    # overload: graceful degradation (TTL shedding, bounded queue,
    # pressure preemption) must RAISE in-deadline goodput over the
    # FIFO-forever baseline, never trading token fidelity (results land
    # in BENCH_overload.json for cross-PR tracking)
    with Timer() as t:
        ovl = traffic.overload(quick=True)
    dg = ovl["modes"]["degraded"]
    print(f"smoke_overload,{t.us:.0f},"
          f"goodput_ratio={ovl['goodput_ratio_degraded_vs_baseline']:.2f}x;"
          f"shed_rate={dg['shed_rate']:.2f};"
          f"preemptions={dg['preemptions']};"
          f"parity={ovl['degraded_completed_token_parity']}")
    assert ovl["degraded_completed_token_parity"], \
        "graceful degradation must never trade token fidelity"
    assert ovl["goodput_ratio_degraded_vs_baseline"] >= 1.2, \
        "degradation must raise in-deadline goodput >= 1.2x over the " \
        "FIFO-forever baseline under overload " \
        f"(got {ovl['goodput_ratio_degraded_vs_baseline']:.2f}x)"


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="subset of apps/steps (CI-speed)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-shape smoke of online/sweep/traffic only "
                         "(benchmark bit-rot check for CI)")
    args = ap.parse_args(argv)
    compile_cache.enable()
    q = args.quick
    if args.smoke:
        smoke()
        return

    print("name,us_per_call,derived")

    from benchmarks import fig1
    with Timer() as t:
        s1 = fig1.run(quick=q)
    print(f"fig1_perf_gap,{t.us:.0f},"
          f"cori_slack={s1['mean_cori_slowdown']:.4f};"
          f"worst_fixed_gap={s1['worst_fixed_gap']:.3f}")

    from benchmarks import fig3
    with Timer() as t:
        s3 = fig3.run(quick=q)
    drs = ";".join(f"{a}:{d['dominant_reuse']:.0f}" for a, d in s3.items())
    print(f"fig3_reuse_histograms,{t.us:.0f},{drs}")

    from benchmarks import fig5
    with Timer() as t:
        s5 = fig5.run(quick=q)
    print(f"fig5_tuning_trials,{t.us:.0f},"
          f"trial_reduction={s5['trial_reduction']:.2f}x;"
          f"cori={s5['cori_mean_trials']:.1f};"
          f"base={s5['baseline_mean_trials']:.1f}")

    from benchmarks import fig6
    with Timer() as t:
        s6 = fig6.run(quick=q)
    ok = all(d["sub_dr_moves_more_data"] for d in s6.values())
    print(f"fig6_system_validation,{t.us:.0f},sub_dr_moves_more_data={ok}")

    from benchmarks import tiering
    with Timer() as t:
        st = tiering.run(quick=q)
    worst = max(v["cori_vs_best_fixed"] for v in st.values())
    print(f"tiering_serving_cori,{t.us:.0f},max_vs_best_fixed={worst:.2f}x")

    from benchmarks import sweep
    with Timer() as t:
        sw = sweep.run(quick=q)
    worst_sw = min(v["speedup"] for v in sw.values())
    err = max(v["max_rel_err"] for v in sw.values())
    print(f"sweep_batched,{t.us:.0f},min_speedup={worst_sw:.1f}x;"
          f"max_rel_err={err:.1e}")

    from benchmarks import online
    with Timer() as t:
        on = online.run(quick=q)
    print(f"online_cori,{t.us:.0f},"
          f"vs_best_fixed_steady={on['online_vs_best_fixed_steady']:.3f};"
          f"converge_steps={on['online']['time_to_converge_steps']};"
          f"cycles={on['online']['tune_cycles']}")

    from benchmarks import traffic
    with Timer() as t:
        tr = traffic.run(quick=q)
    print(f"traffic_sched,{t.us:.0f},"
          f"vs_best_fixed_steady={tr['online_vs_best_fixed_steady']:.3f};"
          f"token_identical={tr['token_parity']['token_identical']};"
          f"completed={tr['requests']['completed']}")

    with Timer() as t:
        ho = traffic.hostile(quick=q)
    print(f"traffic_hostile,{t.us:.0f},max_regret={ho['max_regret']:.3f};"
          f"guard_reverted={ho['poisoned_trial']['reverted']};"
          f"tune_cycles={ho['tuner']['tune_cycles']};"
          f"guard_trips={ho['tuner']['guard_trips']}")

    with Timer() as t:
        sp = traffic.serving_perf(quick=q)
    print(f"serving_macro,{t.us:.0f},"
          f"macro_speedup={sp['speedup_macro_vs_per_token']:.2f}x;"
          f"macro_tok_s={sp['modes']['macro']['tokens_per_sec']:.0f};"
          f"parity={sp['token_identical_all_modes']}")

    with Timer() as t:
        ovl = traffic.overload(quick=q)
    print(f"serving_overload,{t.us:.0f},"
          f"goodput_ratio={ovl['goodput_ratio_degraded_vs_baseline']:.2f}x;"
          f"shed_rate={ovl['modes']['degraded']['shed_rate']:.2f};"
          f"parity={ovl['degraded_completed_token_parity']}")

    from benchmarks import roofline
    with Timer() as t:
        rr = roofline.run(quick=q)
    n = len(rr["rows"])
    if n:
        best = max(r["roofline_fraction"] for r in rr["rows"])
        print(f"roofline_terms,{t.us:.0f},cells={n};best_fraction={best:.3f}")
    else:
        print(f"roofline_terms,{t.us:.0f},cells=0 (run repro.launch.dryrun)")


if __name__ == "__main__":
    main()
