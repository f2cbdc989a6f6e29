"""Sweep-engine benchmark: event-driven loop vs the grid backends.

Runs the same Fig-2-style scenario matrix (nine barrier policies — five
static protocols plus the four adaptive members — × five straggler
fractions, matched seeds) through every engine — a Python loop over the
discrete-event :func:`~repro.core.simulator.run_simulation` (the
*before*), the vectorized NumPy :func:`~repro.core.vector_sim.run_sweep`,
its jax backend (donated chunked scans with the fused full tick, sharded
over the host's device mesh), and the Pallas tick kernel
(``PSP_TICK_IMPL=interpret`` through the Pallas interpreter on CPU; the
real Mosaic kernel when a TPU is attached) — checks the engines agree at
the distribution level, and records wall-clock plus speedups in
``BENCH_sweep.json`` at the repo root.  Grid-engine rows carry separate
**compile** and **run** phases so a compile-time regression can't hide
inside a throughput number (and vice versa).  Schema and regeneration
flags are documented in ``docs/BENCHMARKS.md``.

On CPU hosts the benchmark forces an ``xla_force_host_platform_device_count``
mesh (one device per core, capped at 8) **before jax initialises**, so the
jax row exercises the sharded multi-device path exactly as a TPU pod slice
would; set ``PSP_BENCH_HOST_DEVICES=0`` to disable, or any value to pin
the mesh size.  ``--mesh RxN`` (or ``PSP_SWEEP_MESH``) factorizes those
devices into a 2-D rows × nodes placement for the jax rows; every
jax-family row records its resolved ``mesh`` / ``mesh_axes``.  A
100k-node pBSP-vs-SSP smoke sweep (``jax_100k`` row) always rides along —
the node-sharded regime no event loop could reach, reported as
machine-comparable per-device node-step throughput.

    PYTHONPATH=src python -m benchmarks.sweep_bench [--full] [--no-pallas]
        [--mesh RxN]
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict

from benchmarks import _host_mesh  # noqa: F401  (must precede jax import)

import jax  # noqa: E402  (after the device-count bootstrap, by design)

from repro.core.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.barriers import make_barrier            # noqa: E402
from repro.core.simulator import SimConfig, run_simulation  # noqa: E402
from repro.core.sweep_plan import parse_mesh, resolve_mesh  # noqa: E402
from repro.core.vector_sim import run_sweep             # noqa: E402

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_sweep.json")

FIVE = ("bsp", "ssp", "asp", "pbsp", "pssp")
ADAPTIVE = ("dssp", "ebsp", "apbsp", "apssp")
NINE = FIVE + ADAPTIVE
FRACS = (0.0, 0.05, 0.1, 0.2, 0.3)


def _configs(full: bool):
    """The Fig-2 scenario matrix (paper scale under ``--full``).

    Nine barrier rows: the five static protocols plus the four adaptive
    policies (whose per-row state rides in the scanned carry on the grid
    engines), so the gate times the policy-threading overhead too.
    """
    n, dur, dim = (1000, 40.0, 100) if full else (100, 20.0, 32)
    beta = max(1, n // 100)
    return [SimConfig(n_nodes=n, duration=dur, dim=dim, seed=3,
                      straggler_frac=frac,
                      barrier=make_barrier(name, staleness=4,
                                           sample_size=beta))
            for name in NINE for frac in FRACS]


def _mesh_fields(B: int, P: int) -> Dict:
    """Mesh metadata for a jax-engine row: the resolved placement.

    The regression gate (``tools/check_bench.py``) *requires* these on
    every jax-family row and normalizes throughput per device, so
    baselines transfer across mesh shapes/sizes.
    """
    rows, nodes = resolve_mesh(B, P)
    return {"n_devices": rows * nodes,
            "mesh": [rows, nodes],
            "mesh_axes": {"rows": rows, "nodes": nodes}}


def _100k_configs():
    """The 100k-node pBSP-vs-SSP smoke pair — the regime no event loop
    could touch (the paper's §6 "internet scale" claim).

    ``sample_size=1`` keeps the β-sample draw on the O(P) fast path —
    a P×P score matrix at P = 100 000 would be 40 GB — and a 1-second
    horizon bounds the grid at 50 ticks; the point of the row is the
    placement (node-sharded state at P = 100 000), not the physics.
    """
    return [SimConfig(n_nodes=100_000, duration=1.0, dim=4, batch=2,
                      seed=3, straggler_frac=0.1,
                      barrier=make_barrier(name, staleness=4,
                                           sample_size=1))
            for name in ("pbsp", "ssp")]


def hundred_k_row() -> Dict:
    """Time the 100k-node smoke sweep on the jax engine → one bench row.

    Throughput is reported as ``node_steps_per_device_sec`` — completed
    node steps across the sweep, per device, per second — so the number
    is comparable across mesh factorizations of different sizes (the
    numerator is bit-identical across factorizations by the equivalence
    suite's contract; only wall-clock and device count vary).
    """
    from repro.core import vector_sim_jax
    cfgs = _100k_configs()
    # one scenario row per merge group: the rows axis is useless here, so
    # default every device to the nodes axis (an explicit --mesh /
    # PSP_SWEEP_MESH still wins)
    mesh_before = os.environ.get("PSP_SWEEP_MESH")
    if mesh_before is None:
        os.environ["PSP_SWEEP_MESH"] = f"1x{len(jax.devices())}"
    # the Pallas tick cannot hold P = 100 000 in VMEM (it refuses at
    # trace time), so this row names the reference tick explicitly
    impl_before = os.environ.get("PSP_TICK_IMPL")
    os.environ["PSP_TICK_IMPL"] = "ref"
    try:
        t0 = time.time()
        run_sweep(cfgs, backend="jax")
        compile_s = time.time() - t0
        best = float("inf")
        for _ in range(2):
            t0 = time.time()
            res = run_sweep(cfgs, backend="jax")
            best = min(best, time.time() - t0)
        steps = int(sum(int(r.steps.sum()) for r in res))
        # merge groups run one scenario row each → B=1 governs the clamp
        row = _mesh_fields(1, cfgs[0].n_nodes)
    finally:
        if mesh_before is None:
            os.environ.pop("PSP_SWEEP_MESH", None)
        if impl_before is None:
            os.environ.pop("PSP_TICK_IMPL", None)
        else:
            os.environ["PSP_TICK_IMPL"] = impl_before
        vector_sim_jax._compiled_chunk.cache_clear()
    row.update({
        "tick_impl": "ref",
        "seconds": best,
        "compile_seconds": max(compile_s - best, 0.0),
        "n_nodes": cfgs[0].n_nodes,
        "n_configs": len(cfgs),
        "barriers": [c.barrier.name for c in cfgs],
        "total_node_steps": steps,
        "node_steps_per_device_sec":
            steps / max(best, 1e-9) / row["n_devices"],
        "mean_progress": {c.barrier.name: r.mean_progress
                          for c, r in zip(cfgs, res)},
    })
    return row


def _timed_grid(cfgs, backend: str, impl: str | None = None):
    """(compile_s, run_s, results) for one grid engine.

    The first full-matrix call pays jit tracing + compilation — recorded
    as the *compile* phase (numpy's is import/BLAS warm-up, ~0).  The
    *run* phase is then timed **best-of-3**: a sweep is ~1–2 s, so one
    stray scheduler hiccup would otherwise dominate the measurement —
    and the CI bench-regression gate (``tools/check_bench.py``) compares
    these numbers across runs.  The 20× longer event-loop reference
    stays single-shot (its relative noise is small).
    """
    from repro.core import vector_sim_jax
    env_before = os.environ.get("PSP_TICK_IMPL")
    if impl is not None:
        os.environ["PSP_TICK_IMPL"] = impl
    try:
        t0 = time.time()
        run_sweep(cfgs, backend=backend)
        compile_s = time.time() - t0
        best = float("inf")
        for _ in range(3):
            t0 = time.time()
            res = run_sweep(cfgs, backend=backend)
            best = min(best, time.time() - t0)
        # first-call total minus steady-state run ≈ trace+compile cost
        return max(compile_s - best, 0.0), best, res
    finally:
        if impl is not None:
            if env_before is None:
                os.environ.pop("PSP_TICK_IMPL", None)
            else:
                os.environ["PSP_TICK_IMPL"] = env_before
        vector_sim_jax._compiled_chunk.cache_clear()


def sweep_speedup(full: bool = False, backend: str | None = None,
                  pallas: bool = True,
                  out_path: str | None = OUT_PATH,
                  mesh: str | None = None) -> Dict:
    """Time the Fig-2 sweep on all engines and dump ``BENCH_sweep.json``.

    ``backend`` is accepted for harness uniformity and ignored — this
    benchmark's whole point is timing every engine against the others.
    ``pallas=False`` skips the Pallas-tick row (it adds an extra
    compile of the interpreted kernel on CPU).  ``out_path`` redirects
    the JSON dump (``None`` skips it) — the CI bench-regression gate
    writes a *fresh* file and compares it against the committed baseline
    with ``tools/check_bench.py``, and the ``benchmarks.run`` harness
    passes ``None`` so a local harness run never overwrites the
    committed baseline; only the standalone CLI (the documented
    baseline-regeneration command) writes ``BENCH_sweep.json``.

    ``mesh`` pins a 2-D ``RxN`` rows × nodes factorization for the jax
    grid rows (exported as ``PSP_SWEEP_MESH`` for the duration of the
    run; see :mod:`repro.core.sweep_plan`).  Every jax-family row — the
    Fig-2 matrix, the Pallas-tick row, and the always-present 100k-node
    ``jax_100k`` smoke row — records the *resolved* placement under
    ``mesh`` / ``mesh_axes``; results are bit-identical across
    factorizations, so the mesh only moves the timings.
    """
    cache_on = enable_compile_cache() is not None
    mesh_before = os.environ.get("PSP_SWEEP_MESH")
    if mesh is not None:
        parse_mesh(mesh)                       # reject typos loudly, now
        os.environ["PSP_SWEEP_MESH"] = mesh
    try:
        return _sweep_speedup(full, pallas, out_path, cache_on)
    finally:
        if mesh is not None:
            if mesh_before is None:
                os.environ.pop("PSP_SWEEP_MESH", None)
            else:
                os.environ["PSP_SWEEP_MESH"] = mesh_before


def _sweep_speedup(full: bool, pallas: bool, out_path: str | None,
                   cache_on: bool) -> Dict:
    cfgs = _configs(full)
    compile_t, timings, per_engine = {}, {}, {}
    compile_t["numpy"], timings["numpy"], per_engine["numpy"] = \
        _timed_grid(cfgs, "numpy")
    # baseline jax row pins the jnp reference tick — on TPU "auto" would
    # dispatch the Pallas kernel and the pallas row would compare the
    # kernel against itself
    compile_t["jax"], timings["jax"], per_engine["jax"] = \
        _timed_grid(cfgs, "jax", impl="ref")
    if pallas:
        # Pallas tick kernel: the interpreter lowers it to XLA on CPU, so
        # this times kernel *semantics* end-to-end; on a TPU host the same
        # row times the Mosaic-compiled kernel (named, never "auto")
        impl = "pallas" if jax.default_backend() == "tpu" else "interpret"
        compile_t["pallas"], timings["pallas"], per_engine["pallas"] = \
            _timed_grid(cfgs, "jax", impl=impl)
    t0 = time.time()
    ev = [run_simulation(c) for c in cfgs]
    timings["event"] = time.time() - t0

    def max_dev(results):
        rel = [v.mean_progress / max(e.mean_progress, 1e-9)
               for e, v in zip(ev, results)]
        return max(abs(r - 1.0) for r in rel)

    def amortized(name):
        # end-to-end speedup *including* the compile paid this run: with a
        # warm persistent cache compile_seconds collapses toward zero and
        # this converges on the steady-state speedup_vs_event — the
        # compile-amortized throughput the ROADMAP item asks for
        return timings["event"] / max(timings[name] + compile_t[name], 1e-9)

    # merge groups shard B = per-group row count; the static five are the
    # largest group, so report the placement that matrix resolved to
    grid_mesh = _mesh_fields(len(FRACS) * len(FIVE), cfgs[0].n_nodes)
    engines = {
        "event": {"seconds": timings["event"]},
        "numpy": {"seconds": timings["numpy"],
                  "compile_seconds": compile_t["numpy"],
                  "speedup_vs_event":
                      timings["event"] / max(timings["numpy"], 1e-9),
                  "amortized_speedup_vs_event": amortized("numpy"),
                  "max_progress_deviation": max_dev(per_engine["numpy"])},
        "jax": {"seconds": timings["jax"],
                "compile_seconds": compile_t["jax"],
                **grid_mesh,
                "speedup_vs_event":
                    timings["event"] / max(timings["jax"], 1e-9),
                "amortized_speedup_vs_event": amortized("jax"),
                "throughput_vs_numpy":
                    timings["numpy"] / max(timings["jax"], 1e-9),
                "max_progress_deviation": max_dev(per_engine["jax"])},
    }
    if pallas:
        engines["pallas"] = {
            "seconds": timings["pallas"],
            "compile_seconds": compile_t["pallas"],
            "tick_impl": ("pallas" if jax.default_backend() == "tpu"
                          else "interpret"),
            **grid_mesh,
            "speedup_vs_event":
                timings["event"] / max(timings["pallas"], 1e-9),
            "amortized_speedup_vs_event": amortized("pallas"),
            "throughput_vs_jax_ref":
                timings["jax"] / max(timings["pallas"], 1e-9),
            "max_progress_deviation": max_dev(per_engine["pallas"]),
        }
    engines["jax_100k"] = hundred_k_row()
    grid = [name for name in ("numpy", "jax", "pallas") if name in engines]
    res = {
        "sweep": "fig2_stragglers",
        "n_configs": len(cfgs),
        "n_nodes": cfgs[0].n_nodes,
        "duration_s": cfgs[0].duration,
        "compile_cache": cache_on,
        "engines": engines,
        # cross-engine summary: every top-level field is an explicit
        # maximum over the grid-engine rows (per-engine values live in
        # the rows themselves) — see docs/BENCHMARKS.md
        "summary": {
            "best_speedup_vs_event": max(
                engines[n]["speedup_vs_event"] for n in grid),
            "max_progress_deviation": max(
                engines[n]["max_progress_deviation"] for n in grid),
        },
    }
    if out_path is not None:
        with open(out_path, "w") as f:
            json.dump(res, f, indent=1)
    return res


def main(argv=None) -> None:
    """CLI entry: ``python -m benchmarks.sweep_bench [--full] [--no-pallas]
    [--out PATH]``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--no-pallas", action="store_true",
                    help="skip the Pallas-tick engine row")
    ap.add_argument("--mesh", default=None, metavar="RxN",
                    help="rows × nodes device factorization for the jax "
                         "rows (e.g. 1x8; default: all devices on the "
                         "rows axis, PSP_SWEEP_MESH overrides)")
    ap.add_argument("--out", default=OUT_PATH,
                    help="JSON output path (default: repo-root "
                         "BENCH_sweep.json; the CI gate writes a fresh "
                         "file and compares via tools/check_bench.py)")
    a = ap.parse_args(argv)
    res = sweep_speedup(full=a.full, pallas=not a.no_pallas, out_path=a.out,
                        mesh=a.mesh)
    e = res["engines"]
    extra = ""
    if "pallas" in e:
        extra = (f"pallas={e['pallas']['seconds']:.2f}s"
                 f"({e['pallas']['tick_impl']}) ")
    hk = e["jax_100k"]
    print(f"event={e['event']['seconds']:.2f}s "
          f"numpy={e['numpy']['seconds']:.2f}s "
          f"jax={e['jax']['seconds']:.2f}s"
          f"[mesh {e['jax']['mesh'][0]}x{e['jax']['mesh'][1]}] "
          f"{extra}"
          f"jax_vs_numpy={e['jax']['throughput_vs_numpy']:.2f}x "
          f"max_dev={res['summary']['max_progress_deviation']:.3f} "
          f"100k={hk['seconds']:.2f}s"
          f"[mesh {hk['mesh'][0]}x{hk['mesh'][1]}, "
          f"{hk['node_steps_per_device_sec']:.0f} node-steps/dev/s]")


if __name__ == "__main__":
    main()
