"""Benchmark harness — one entry per paper figure/table.

Prints ``name,us_per_call,derived`` CSV (one line per benchmark) and dumps
the full series to results/benchmarks/*.json.  Every figure sweep routes
through the vectorized grid engine
(:func:`repro.core.vector_sim.run_sweep`) on the backend selected by
``--backend`` — numpy array ops, or the device-resident jax scan whose
control-plane tick is the fused kernel of :mod:`repro.kernels.psp_tick`
(churn and ragged shapes run natively on both; there is no event-engine
fallback).  Flag reference and the figure → command map live in
``docs/BENCHMARKS.md``.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig1_progress]
                                            [--backend numpy|jax]

``--full`` runs the paper-scale settings (1000 nodes / 40 s / β = 1%);
default is a CI-friendly reduced scale with identical structure.
"""
from __future__ import annotations

import argparse
import json
import os
import time

from benchmarks import _host_mesh  # noqa: F401  (must precede jax import)
from benchmarks import churn_bench, fig45_bounds, figures, sweep_bench
from benchmarks.roofline_bench import print_table, sweep_tick_row, table
from repro.core.compile_cache import enable_compile_cache

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "results",
                       "benchmarks")


def _derived_fig1(res):
    return ("pbsp_vs_bsp_progress="
            f"{res['pbsp']['mean'] / max(res['bsp']['mean'], 1e-9):.2f}")


def _derived_fig1_err(res):
    best = min(res, key=lambda k: res[k]["final"])
    return f"lowest_error={best}:{res[best]['final']:.4f}"


def _derived_fig1_msg(res):
    return ("asp_vs_bsp_updates="
            f"{res['asp']['total'] / max(res['bsp']['total'], 1):.1f}x")


def _derived_fig1_bands(res):
    best = min(res, key=lambda k: res[k]["final_mean"])
    return (f"lowest_error={best}:{res[best]['final_mean']:.4f}"
            f"±{res[best]['final_std']:.4f}")


def _derived_fig2(res):
    worst = res["bsp"][-1]["progress_ratio"]
    rob = res["pbsp"][-1]["progress_ratio"]
    return f"at30pct: bsp={worst:.2f} pbsp={rob:.2f}"


def _derived_fig2c(res):
    return (f"at16x: bsp={res['bsp'][-1]['progress_ratio']:.2f} "
            f"pbsp={res['pbsp'][-1]['progress_ratio']:.2f}")


def _derived_fig3(res):
    return (f"largest: bsp={res['bsp'][-1]['progress_pct']:.0f}% "
            f"pssp={res['pssp'][-1]['progress_pct']:.0f}%")


def _derived_sweep(res):
    keys = sorted(res, key=lambda k: int(k.split("=")[1]))
    return (f"spread beta0={res[keys[0]]['spread']} "
            f"beta_max={res[keys[-1]]['spread']}")


BENCHES = [
    ("fig1_progress", figures.fig1_progress, _derived_fig1),
    ("fig1_sample_sweep", figures.fig1_sample_sweep, _derived_sweep),
    ("fig1_error", figures.fig1_error, _derived_fig1_err),
    # bands are pinned to the numpy backend regardless of --backend: the
    # jax backend shares dynamics draws across rows, which would understate
    # seed-to-seed spread (see benchmarks/figures.py docstring)
    ("fig1_error_bands",
     lambda full=False, backend="numpy": figures.fig1_error_bands(full=full),
     _derived_fig1_bands),
    ("fig1_messages", figures.fig1_messages, _derived_fig1_msg),
    ("fig2_stragglers", figures.fig2_stragglers, _derived_fig2),
    ("fig2_slowness", figures.fig2_slowness, _derived_fig2c),
    ("fig3_scalability", figures.fig3_scalability, _derived_fig3),
    ("fig4_mean_bound", fig45_bounds.fig4_mean_bound,
     lambda res: fig45_bounds.derived_summary()),
    ("fig5_variance_bound",
     lambda full=False, backend="numpy": fig45_bounds.fig5_variance_bound(),
     lambda res: fig45_bounds.derived_summary()),
    # out_path=None: the harness persists the result itself below; only
    # the standalone sweep_bench CLI regenerates the committed CI-gate
    # baseline BENCH_sweep.json
    ("sweep_engine",
     lambda full=False, backend=None:
         sweep_bench.sweep_speedup(full=full, out_path=None),
     lambda res: f"speedup={res['summary']['best_speedup_vs_event']:.1f}x "
                 f"max_dev={res['summary']['max_progress_deviation']:.3f}"),
    # elastic SPMD trainer under Poisson churn: the convergence-vs-
    # virtual-wall-clock trade-off with a dynamic worker set
    ("elastic_churn", churn_bench.elastic_churn,
     lambda res: "err@T " + " ".join(
         f"{k}={res[k]['final_error']:.3f}" for k in ("bsp", "pssp", "asp"))),
    # adaptive-vs-static reshape of the same runs (elastic_churn result
    # is cached, so the 18 trainer runs are not repeated)
    ("fig6_adaptive_churn", figures.fig6_adaptive_churn,
     lambda res: "dominant " + (",".join(
         name for name, s in res["scoreboard"]["stragglers"].items()
         if s["dominates"]) or "none") + " (stragglers)"),
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale (1000 nodes, 40s)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--backend", default="numpy", choices=("numpy", "jax"),
                    help="grid engine for the figure sweeps")
    ap.add_argument("--skip-roofline", action="store_true")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax-profiler trace of the whole run "
                         "into DIR (open with TensorBoard/Perfetto); perf "
                         "PRs argue from these traces")
    a = ap.parse_args(argv)
    os.makedirs(OUT_DIR, exist_ok=True)
    enable_compile_cache()

    if a.profile:
        import jax
        jax.profiler.start_trace(a.profile)
    try:
        _run_benches(a)
    finally:
        if a.profile:
            import jax
            jax.profiler.stop_trace()
            print(f"profile,0,trace_dir={a.profile}")


def _run_benches(a) -> None:
    """Execute the selected benchmarks (split out so ``--profile`` can
    bracket every compiled region in one trace)."""
    print("name,us_per_call,derived")
    for name, fn, derive in BENCHES:
        if a.only and name != a.only:
            continue
        t0 = time.time()
        res = fn(full=a.full, backend=a.backend)
        us = (time.time() - t0) * 1e6
        with open(os.path.join(OUT_DIR, name + ".json"), "w") as f:
            json.dump(res, f)
        print(f"{name},{us:.0f},{derive(res)}")

    if not a.skip_roofline and (a.only in (None, "roofline")):
        t0 = time.time()
        rows = table("single")
        if not rows:
            print("note: no dry-run artifacts (run repro.launch.dryrun); "
                  "roofline table holds the sweep-tick row only")
        # the sweep engine's own hot path sits in the same table as the
        # model archs (ROADMAP: sweep-kernel roofline row)
        rows.append(sweep_tick_row())
        ok = [r for r in rows if r["status"] == "ok"]
        with open(os.path.join(OUT_DIR, "roofline.json"), "w") as f:
            json.dump(rows, f, indent=1)
        counts = {}
        for r in ok:
            counts[r["bottleneck"]] = counts.get(r["bottleneck"], 0) + 1
        us = (time.time() - t0) * 1e6
        print(f"roofline,{us:.0f},combos={len(ok)} bottlenecks={counts}")


if __name__ == "__main__":
    main()
