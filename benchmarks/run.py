"""Run the full benchmark suite: one experiment per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig3a]

Writes experiments/bench/results.json and prints a per-figure summary
with the corresponding paper claim and whether the reproduction agrees
qualitatively.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import bench_walltime, suite  # noqa: E402
from repro.launch import compile_cache  # noqa: E402

OUT = os.path.join(os.path.dirname(__file__), "..", "experiments", "bench")

# per-figure artifact names for --all-tiny / --all-full: the files
# tools/bench_check.py diffs against benchmarks/baselines/ and CI
# uploads under the bench-* artifact pattern.  Adding a benchmark to
# the smoke job = adding it to suite.TINY_CAPABLE (+ a baseline).
FIG_FILES = {
    "dispatch": "BENCH_dispatch.json",
    "grouped_capacity": "BENCH_grouped_capacity.json",
    "tp_crossover": "BENCH_tp.json",
    "train_grad": "BENCH_train_grad.json",
    "pattern_evolution": "BENCH_pattern_evolution.json",
    "skewed_patterns": "BENCH_skewed_patterns.json",
    "serving": "BENCH_serving.json",
}

CLAIMS = {
    "table3": "paper Table 3: static > dynamic at every (b, dtype); "
              "speedup grows with b; fp32 ratios exceed fp16",
    "fig3a": "paper Fig 3a: sparse ~flat vs density (near-perfect "
             "scaling), dense degrades linearly in useful FLOP/s",
    "fig4a": "paper Fig 4a: throughput grows with block size "
             "(2.1x b=4, 6.6x b=16 on IPU)",
    "fig4b": "paper Fig 4b: sparse speedup improves with feature size",
    "fig4c": "paper Fig 4c power law 0.0013*m^0.59*d^-0.54*b^0.50: "
             "same exponent signs (m+, d-, b+)",
    "fig7": "paper Fig 7: speedup grid favours large m, low d, large b",
    "fig2": "paper Fig 2: dense TFLOP/s saturates with batch size",
    "occupancy": "TPU-specific (DESIGN.md S2): clustered patterns pack "
                 "into near-full MXU tiles, scattered ones do not",
    "cpu_walltime": "hardware-agnostic ordering check on real timers",
    "dispatch": "paper Table 3 as runtime plans: static routes win at "
                "low density / large blocks, dense at high density",
    "grouped_capacity": "paper §3.3/A.2 bucket sizing: expected-tiles + "
                        "headroom capacity beats the safe worst case at "
                        "low density; overflow risk is priced, not "
                        "ignored",
    "tp_crossover": "paper Fig 1a at mesh scale: k-sharded TP SpMM "
                    "(local block work + one reduction) crosses over "
                    "the unsharded route as m grows; the verdict is "
                    "measured (gspmd vs shard_map vs unsharded race), "
                    "not modeled",
    "train_grad": "paper §3.2 extended to training: the backward "
                  "products (transposed-pattern SpMM + block SDDMM) "
                  "ride the same pre-planned fast path as the forward, "
                  "so the fwd+bwd triple beats the dense triple at low "
                  "density and the win grows as density falls",
    "pattern_evolution": "dynamic sparse training on static plans: a "
                         "RigL topology update is an incremental "
                         "MatmulPlan.evolve (host re-pack, verdicts "
                         "reused, zero measurements) instead of a "
                         "from-scratch re-plan, and the evolved plan "
                         "keeps the static fwd+bwd win over dense",
    "skewed_patterns": "load-balanced walks (PR 8): on row-skewed "
                       "patterns (imbalance >= 2) the balanced routes "
                       "beat the uniform walk >= 1.2x and win the plan "
                       "race at the acceptance point; on uniform masks "
                       "they never cost more than the 2% swizzle "
                       "overhead (ratio >= 0.95)",
    "serving": "serving layer (PR 10): the paper's static-sparse FFN "
               "speedup survives end-to-end continuous-batching "
               "serving (requests/sec at the inter-token-latency SLO "
               "beats the dense stack), and the cost-model bucket "
               "ladder beats pad-to-max prefill",
}


def _check(fig, recs):
    """Qualitative agreement checks -> (ok, note)."""
    if fig == "table3":
        stat = {(r["block_size"], r["dtype"]): r["speedup_vs_dense"]
                for r in recs if r["mode"] == "static-clustered"}
        dyn = {(r["block_size"], r["dtype"]): r["speedup_vs_dense"]
               for r in recs if r["mode"] == "dynamic"}
        grp = {(r["block_size"], r["dtype"]): r["speedup_vs_dense"]
               for r in recs if r["mode"] == "dynamic-grouped"}
        ok = all(stat[k] >= dyn[k] for k in stat)          # static > dynamic
        ok &= all(stat[k] >= grp[k] for k in stat)
        ok &= dyn[(16, "fp16")] > dyn[(4, "fp16")] > dyn[(1, "fp16")]
        ok &= grp[(16, "fp16")] > 1.0   # TPU-native dynamic beats dense
        return ok, (f"b16,fp16: static={stat[(16, 'fp16')]}x "
                    f"dynamic-grouped={grp[(16, 'fp16')]}x "
                    f"dynamic-blockwise={dyn[(16, 'fp16')]}x (blockwise "
                    f"slots under-fill the 128x128 MXU -- see DESIGN.md)")
    if fig == "fig4a":
        dyn = {r["b"]: r["tflops"] for r in recs if r["mode"] == "dynamic"}
        sca = {r["b"]: r["tflops"] for r in recs
               if r["mode"] == "static-scattered-lowd"}
        ok = dyn[16] > dyn[4] > dyn[1] and sca[16] >= sca[4] >= sca[1]
        return ok, (f"dynamic tflops b1/4/16: {dyn[1]}/{dyn[4]}/{dyn[16]}; "
                    f"scattered-static: {sca[1]}/{sca[4]}/{sca[16]} "
                    f"(clustered static is b-independent on MXU -- packing)")
    if fig == "fig4b":
        sp = [r["speedup"] for r in recs]
        return all(b >= a * 0.95 for a, b in zip(sp, sp[1:])), \
            f"speedups {sp}"
    if fig == "fig4c":
        r = recs[0]
        ok = r["m_exp"] > 0 and r["d_exp"] < 0
        return ok, (f"ours m^{r['m_exp']} d^{r['d_exp']} b^{r['b_exp']} "
                    f"vs paper m^0.59 d^-0.54 b^0.50 (b-exp ~0 on MXU: "
                    f"128-tile packing absorbs the block size)")
    if fig == "fig3a":
        stat = sorted((r["density"], r["tflops"]) for r in recs
                      if r.get("mode") == "static" and r.get("b") == 16)
        lo, hi = stat[0][1], stat[-1][1]
        return hi / max(lo, 1e-9) < 4.0, \
            f"static b16 tflops across densities: {lo}..{hi}"
    if fig == "cpu_walltime":
        return all(r["static_faster_than_dynamic"] for r in recs), \
            "static < dynamic wall-clock on every config"
    if fig == "occupancy":
        by = {(r["b"], r["clustered"]): r["occupancy"] for r in recs}
        return by[(16, True)] > 5 * by[(16, False)], \
            f"b=16 occupancy clustered {by[(16, True)]} vs " \
            f"scattered {by[(16, False)]}"
    if fig == "dispatch":
        low = [r["chosen"] for r in recs if r["kind"] == "static"
               and r["density"] <= 1 / 16 and r["b"] >= 16]
        ok = bool(low) and any(c.startswith("static") for c in low)
        return ok, (f"{len(recs)} planned decisions; low-density b>=16 "
                    f"static routes: {sorted(set(low))}")
    if fig == "grouped_capacity":
        # planned capacity must never lose to the worst case, and must
        # WIN somewhere at <=10% density with the default headroom (the
        # PR acceptance criterion: dynamic_grouped can only take the
        # low-density dispatch race if its planned bucket is cheaper)
        never_worse = all(r["speedup_vs_worst"] >= 1.0 for r in recs)
        wins = [r for r in recs if r["density"] <= 0.1
                and r["headroom"] == 1.25 and r["speedup_vs_worst"] > 1.1]
        best = max(recs, key=lambda r: r["speedup_vs_worst"])
        return never_worse and bool(wins), (
            f"{len(wins)} planned-capacity wins at d<=10% "
            f"(best {best['speedup_vs_worst']}x at m={best['m']} "
            f"b={best['b']} d={best['density']:.4f} "
            f"headroom={best['headroom']}, P[overflow]="
            f"{best['overflow_p']})")
    if fig == "train_grad":
        # fwd+bwd speedup grows as density falls per (m, b), and sparse
        # training must win somewhere at d<=1/16 with b>=16; the dL/dW
        # verdict must leave the dense product at the lowest density
        by = {}
        for r in recs:
            by.setdefault((r["m"], r["b"]), []).append(
                (r["density"], r["train_speedup_vs_dense"]))
        mono = all(b2 >= a2 * 0.999 for series in by.values()
                   for (_, a2), (_, b2) in
                   zip(sorted(series, reverse=True),
                       sorted(series, reverse=True)[1:]))
        wins = [r for r in recs if r["density"] <= 1 / 16
                and r["b"] >= 16 and r["train_speedup_vs_dense"] > 1.0]
        lowd = [r for r in recs
                if r["density"] == min(x["density"] for x in recs)]
        sparse_dw = any(r["dv_route"] != "sddmm_dense" for r in lowd)
        best = max(recs, key=lambda r: r["train_speedup_vs_dense"])
        return bool(wins) and mono and sparse_dw, (
            f"{len(wins)} fwd+bwd wins at d<=1/16 b>=16 (best "
            f"{best['train_speedup_vs_dense']}x at m={best['m']} "
            f"b={best['b']} d={best['density']:.4f}: "
            f"fwd={best['fwd_route']} dx={best['dx_route']} "
            f"dW={best['dv_route']})")
    if fig == "pattern_evolution":
        # the tentpole invariant: every in-threshold evolve chain runs
        # zero route decisions / measurement events; evolve must be
        # cheaper than a measured re-plan everywhere; and the evolved
        # plan must still beat the dense training step at d<=1/16 b>=16
        no_events = all(r["evolve_measurements"] == 0 for r in recs)
        cheaper = all(r["replan_vs_evolve"] > 1.0 for r in recs)
        wins = [r for r in recs if r["density"] <= 1 / 16
                and r["b"] >= 16 and r["step_speedup_vs_dense"] > 1.0]
        best = max(recs, key=lambda r: r["step_speedup_vs_dense"])
        return no_events and cheaper and bool(wins), (
            f"{sum(r['generations'] for r in recs)} evolves, "
            f"{sum(r['evolve_measurements'] for r in recs)} measurement "
            f"events; evolve beats measured re-plan on all "
            f"{len(recs)} points; {len(wins)} evolved-plan wins at "
            f"d<=1/16 b>=16 (best {best['step_speedup_vs_dense']}x at "
            f"m={best['m']} b={best['b']} d={best['density']:.4f})")
    if fig == "skewed_patterns":
        # the PR 8 acceptance criterion: balanced routes beat the
        # uniform walk >= 1.2x wherever imbalance >= 2 (both families),
        # never lose more than the swizzle overhead on uniform masks,
        # and actually WIN the race at a power-law point with m >= 4096,
        # b = 16, d <= 1/16
        skewed = [r for r in recs if r["imbalance"] >= 2.0]
        uniform = [r for r in recs if r["mask"] == "uniform"]
        wins = (bool(skewed)
                and all(r["static_balance_ratio"] >= 1.2
                        and r["dynamic_balance_ratio"] >= 1.2
                        for r in skewed))
        holds = all(r["static_balance_ratio"] >= 0.95
                    and r["dynamic_balance_ratio"] >= 0.95
                    for r in uniform)
        acc = [r for r in skewed
               if r["mask"] == "power_law" and r["m"] >= 4096
               and r["b"] == 16 and r["density"] <= 1 / 16
               and r["chosen"].endswith("balanced")]
        best = max(recs, key=lambda r: r["static_balance_ratio"])
        return wins and holds and bool(acc), (
            f"{len(skewed)} skewed points all >= 1.2x, "
            f"{len(uniform)} uniform points all >= 0.95x; race won by "
            f"{acc[0]['chosen'] if acc else 'NOTHING'} at the "
            f"acceptance point (best {best['static_balance_ratio']}x "
            f"at mask={best['mask']} m={best['m']} b={best['b']} "
            f"imbalance={best['imbalance']})")
    if fig == "serving":
        # the serving acceptance: every arm meets its SLO somewhere on
        # the batch sweep, bucketed prefill beats pad-to-max, and the
        # sparse-FFN arm sustains more requests/sec than the dense arm
        # at the SAME (dense-derived) SLO
        slo_met = all(r["batch_at_slo"] is not None for r in recs)
        bucketing = all(r["throughput_vs_padmax"] > 1.0 for r in recs)
        sp = [r for r in recs if "serving_speedup_vs_dense" in r]
        wins = bool(sp) and all(r["serving_speedup_vs_dense"] > 1.0
                                for r in sp)
        best = max(sp, key=lambda r: r["serving_speedup_vs_dense"]) \
            if sp else None
        return slo_met and bucketing and wins, (
            f"{len(recs)} arms all meet the SLO; bucketing beats "
            f"pad-to-max on every arm; sparse serving wins "
            + (f"{best['serving_speedup_vs_dense']}x rps at the SLO "
               f"on {best['model']} (bucket ladder "
               f"{best['buckets']})" if best else "NOWHERE"))
    if fig == "tp_crossover":
        # deterministic side: analytic TP speedup grows with m per
        # (density, n) and crosses 1 somewhere on the grid; measured
        # side (when devices were available) must be finite and the
        # chosen route the argmin of its race
        by = {}
        for r in recs:
            by.setdefault((r["density"], r["n"]), []).append(
                (r["m"], r["est_tp_speedup"]))
        mono = all(b >= a * 0.999 for series in by.values()
                   for (_, a), (_, b) in zip(sorted(series),
                                             sorted(series)[1:]))
        crossed = any(r["est_tp_speedup"] > 1.0 for r in recs)
        measured = [r for r in recs if r["measured_us"]]
        meas_ok = all(
            all(v > 0 for v in r["measured_us"].values())
            for r in measured)
        n_meas_wins = sum(1 for r in measured if r["tp_wins_measured"])
        note = (f"analytic speedup at q=8 grows with m "
                f"({min(r['est_tp_speedup'] for r in recs)}x..."
                f"{max(r['est_tp_speedup'] for r in recs)}x); "
                f"{len(measured)} measured races"
                + (f", TP measured past crossover on {n_meas_wins}"
                   if measured else " (single device: analytic only)"))
        return mono and crossed and meas_ok, note
    return True, ""


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--skip-walltime", action="store_true")
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke grid for experiments that support it "
                         f"(currently: {', '.join(suite.TINY_CAPABLE)})")
    ap.add_argument("--out", default=None,
                    help="also write the records to this JSON path "
                         "(e.g. BENCH_dispatch.json for the CI artifact)")
    ap.add_argument("--all-tiny", action="store_true",
                    help="run every TINY_CAPABLE experiment on its smoke "
                         "grid and write one BENCH_*.json per figure to "
                         "--out-dir (the CI benchmark-smoke entry point)")
    ap.add_argument("--all-full", action="store_true",
                    help="like --all-tiny but on the full grids (nightly)")
    ap.add_argument("--out-dir", default=OUT,
                    help="directory for the per-figure BENCH_*.json files "
                         "written by --all-tiny / --all-full")
    args = ap.parse_args()
    compile_cache.enable()

    all_recs = {}
    if args.all_tiny or args.all_full:
        for fig in suite.TINY_CAPABLE:
            all_recs[fig] = suite.ALL[fig](tiny=bool(args.all_tiny))
    else:
        for fig, fn in suite.ALL.items():
            if args.only and fig != args.only:
                continue
            if args.tiny and fig in suite.TINY_CAPABLE:
                all_recs[fig] = fn(tiny=True)
            else:
                all_recs[fig] = fn()
        if not args.only and not args.skip_walltime:
            all_recs["cpu_walltime"] = bench_walltime.run()
        elif args.only == "cpu_walltime":
            all_recs["cpu_walltime"] = bench_walltime.run()

    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "results.json"), "w") as f:
        json.dump(all_recs, f, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(all_recs, f, indent=1)
    if args.all_tiny or args.all_full:
        # one file per figure, named exactly like the committed baseline
        # it gates against, so `tools/bench_check.py <out-dir>/BENCH_*`
        # works unmodified
        os.makedirs(args.out_dir, exist_ok=True)
        for fig, recs in all_recs.items():
            path = os.path.join(args.out_dir,
                                FIG_FILES.get(fig, f"BENCH_{fig}.json"))
            with open(path, "w") as f:
                json.dump({fig: recs}, f, indent=1)
            print(f"wrote {path}")

    failures = 0
    for fig, recs in all_recs.items():
        ok, note = _check(fig, recs)
        status = "AGREES" if ok else "DISAGREES"
        failures += 0 if ok else 1
        print(f"[{fig:12s}] {status:9s} {note}")
        print(f"              claim: {CLAIMS.get(fig, '')}")
    print(f"\nwrote {os.path.join(OUT, 'results.json')} "
          f"({sum(len(v) for v in all_recs.values())} records); "
          f"{failures} qualitative disagreements")
    return 0 if failures == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
