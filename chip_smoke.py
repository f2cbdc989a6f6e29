#!/usr/bin/env python3
"""On-chip smoke test: the PSP system's two hot paths on one TPU chip.

Runs in one process, through the entry points a user calls:

1. **Sweep engine** — ``run_sweep(configs, backend="jax")`` with the
   Pallas tick compiled by Mosaic (``PSP_TICK_IMPL=pallas``, never
   ``auto``), at two sizes: the Fig-2 matrix (45 configs, P = 100) and
   the paper's own evaluation (``configs/psp_linear.py``: P = 1000,
   d = 1000, β = 10, 40 s, minibatch 8; nine policies at straggler
   fraction 0.1).  Each is checked against the same sweep with
   ``PSP_TICK_IMPL=ref`` on the same chip: per-node steps, total updates
   and control messages identical, error traces within
   :data:`ERR_ATOL`.
2. **PSP trainer** — ``repro.launch.train.run`` at the full width of
   qwen2-0.5b (random weights from ``--seed``): pBSP, 2 workers, batch 2,
   seq 512, 3 ticks.  Every tick's loss must be finite, some gradient
   push must land, and the server params must change.

Each phase prints its figures (device kind, tick impl, compile and run
seconds, ``peak_bytes_in_use``); the last line of stdout is one JSON
object ``{"ok": true, "device": {...}}``.  Any failure exits non-zero
and prints no such line; so does a host where JAX finds no TPU, and a
directory that holds this script without the repository.

``--four-chips`` runs only the sweep-mesh path: both sweep sizes on the
``(rows × nodes)`` meshes 4x1 and 1x4, each against the 1x1 sweep in the
same process, and reports whether they match bit for bit.

    python3 chip_smoke.py [--four-chips]
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: error-trace tolerance between the Pallas and reference ticks: both
#: run the same XLA data plane on identical control-plane masks, so only
#: a different fusion of that code could move a bit
ERR_ATOL = 1e-5


class SmokeFailure(Exception):
    """A phase's output is wrong."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _fig2_configs():
    from benchmarks.sweep_bench import _configs
    return _configs(full=False)


def _paper_configs():
    from benchmarks.sweep_bench import NINE
    from repro.configs.psp_linear import CONFIG as C
    from repro.core.barriers import make_barrier
    from repro.core.simulator import SimConfig
    return [SimConfig(n_nodes=C.n_nodes, dim=C.dim, duration=C.duration,
                      batch=8, base_compute=C.base_compute, seed=C.seed,
                      straggler_frac=0.1,
                      barrier=make_barrier(name, staleness=C.ssp_staleness,
                                           sample_size=C.sample_size))
            for name in NINE]


def _sweep(cfgs, impl: str, mesh: str = "1x1"):
    """(seconds, results) of one jax sweep with tick ``impl`` on ``mesh``."""
    from repro.core.vector_sim import run_sweep
    os.environ["PSP_TICK_IMPL"] = impl
    os.environ["PSP_SWEEP_MESH"] = mesh
    t0 = time.perf_counter()
    res = run_sweep(cfgs, backend="jax")
    return time.perf_counter() - t0, res


def _compare(name: str, got, want, atol: float) -> dict:
    """Steps/updates/control identical, errors within ``atol``."""
    import numpy as np
    _check(len(got) == len(want), f"{name}: result count differs")
    err_diff = 0.0
    for i, (g, w) in enumerate(zip(got, want)):
        _check(np.array_equal(g.steps, w.steps), f"{name}: row {i} steps")
        _check(g.total_updates == w.total_updates,
               f"{name}: row {i} total updates {g.total_updates} "
               f"!= {w.total_updates}")
        _check(g.control_messages == w.control_messages,
               f"{name}: row {i} control messages {g.control_messages} "
               f"!= {w.control_messages}")
        _check(bool(np.all(np.isfinite(g.errors))),
               f"{name}: row {i} error trace not finite")
        err_diff = max(err_diff, float(np.max(np.abs(g.errors - w.errors))))
    _check(err_diff <= atol,
           f"{name}: error traces differ by {err_diff} > {atol}")
    return {"rows": len(got), "max_err_diff": err_diff,
            "bit_identical": err_diff == 0.0}


def sweep_phase(name: str, cfgs, dev) -> None:
    """Pallas tick (compile + warm run) against the reference tick."""
    first, _ = _sweep(cfgs, "pallas")
    run_s, got = _sweep(cfgs, "pallas")
    ref_s, want = _sweep(cfgs, "ref")
    cmp = _compare(name, got, want, ERR_ATOL)
    steps = sum(int(r.steps.sum()) for r in got)
    print(f"sweep {name}: " + json.dumps({
        "tick_impl": "pallas", "device_kind": dev.device_kind,
        "configs": len(cfgs), "n_nodes": cfgs[0].n_nodes,
        "dim": cfgs[0].dim, "compile_seconds": first - run_s,
        "run_seconds": run_s, "ref_seconds": ref_s,
        "node_steps": steps,
        "final_error_range": [min(r.final_error for r in got),
                              max(r.final_error for r in got)],
        **cmp, "peak_bytes_in_use": _peak_bytes(dev)}), flush=True)


def trainer_phase(dev) -> None:
    """Full-width qwen2-0.5b PSP training, 3 ticks at W = 2."""
    import numpy as np
    from repro.launch import train
    t0 = time.perf_counter()
    rep = train.run(["--arch", "qwen2-0.5b", "--barrier", "pbsp",
                     "--workers", "2", "--batch", "2", "--seq", "512",
                     "--steps", "3", "--log-every", "1", "--seed", "0"])
    wall = time.perf_counter() - t0
    _check(len(rep["loss"]) == 3, f"trainer: {len(rep['loss'])} ticks")
    _check(all(math.isfinite(x) for x in rep["loss"]),
           f"trainer: non-finite loss {rep['loss']}")
    _check(sum(rep["pushes"]) > 0, "trainer: no gradient push landed")
    changed = int(np.sum(rep["params_before"] != rep["params_after"]))
    _check(changed > 0, "trainer: server params did not change")
    print("trainer qwen2-0.5b pbsp W=2: " + json.dumps({
        "device_kind": dev.device_kind, "loss": rep["loss"],
        "pushes": rep["pushes"], "leaves_changed": changed,
        "leaves": len(rep["params_after"]), "seconds": wall,
        "peak_bytes_in_use": _peak_bytes(dev)}), flush=True)


def four_chip_phase(dev) -> None:
    """Both sweep sizes on 4x1 and 1x4 meshes against 1x1."""
    for name, cfgs in (("fig2", _fig2_configs()),
                       ("paper", _paper_configs())):
        base_s, base = _sweep(cfgs, "pallas", "1x1")
        for mesh in ("4x1", "1x4"):
            secs, got = _sweep(cfgs, "pallas", mesh)
            cmp = _compare(f"{name} {mesh}", got, base, ERR_ATOL)
            print(f"sweep {name} mesh {mesh} vs 1x1: " + json.dumps({
                "tick_impl": "pallas", "device_kind": dev.device_kind,
                "seconds": secs, "seconds_1x1": base_s, **cmp,
                "peak_bytes_in_use": _peak_bytes(dev)}), flush=True)
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4x1 / 1x4 sweep-mesh comparison")
    a = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    try:
        from repro.core.compile_cache import enable_compile_cache
        import benchmarks.sweep_bench  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the repository is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform})",
              file=sys.stderr)
        return 2
    want = 4 if a.four_chips else 1
    if len(devs) < want:
        print(f"chip_smoke: {len(devs)} device(s), need {want}",
              file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
          f"compile cache {enable_compile_cache()}", flush=True)
    try:
        if a.four_chips:
            four_chip_phase(dev)
        else:
            sweep_phase("fig2", _fig2_configs(), dev)
            sweep_phase("paper", _paper_configs(), dev)
            gc.collect()
            trainer_phase(dev)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
