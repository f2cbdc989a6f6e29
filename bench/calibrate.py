#!/usr/bin/env python3
"""Readings from which a cell's limits are set.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 [--control]
        [--fault half_batch --fault no_barrier]

For each seed, in one process on the chip: set up the cell as a run does
(a trainer cell's checked ticks through the window's own step; a sweep
cell's first sweep through ``run_sweep``) and compare with the plain
reference (the sound readings); with ``--control``, the reference in the
next lower precision in the program's place (float8 products for the
trainer, ``Precision.HIGH`` for the sweep's data plane); with
``--fault`` (trainer cells), the reference with that fault planted, in
the program's place.  One JSON line per seed and kind; no measured window.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", action="append", default=[])
    a = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]
    import run
    import compare
    devices = run.device_info(1)
    run.enable_cache()
    wl, cfg = run.cell(a.workload)
    drv = run.module("drivers", f"{wl['driver']}.py")
    ref = run.module("configs", f"{wl['config']}.ref.py")
    for seed in (int(s) for s in a.seeds.split(",")):
        t0 = time.perf_counter()
        sess = drv.setup(wl, cfg, seed)
        t1 = time.perf_counter()
        if wl["driver"] == "sweep":
            got = sess.first
            want = ref.replay(cfg, wl, got["seeds"])
            rows = [("program", compare.sweep_readings(got, want))]
            if a.control:
                rows.append(("control", compare.sweep_readings(
                    ref.replay(cfg, wl, got["seeds"], "high"), want)))
        else:
            seen = sess.seen
            sess.state = sess.step = None
            gc.collect()
            want = ref.replay(cfg, wl, seed)
            g = np.sqrt(np.asarray(want["grad_sq"]))
            rows = [("program", compare.train_readings(wl, seen, want)),
                    ("reference_grad_norm_over_median",
                     dict(enumerate((g / np.median(g)).tolist())))]
            if a.control:
                rows.append(("control", compare.train_readings(
                    wl, ref.replay(cfg, wl, seed, quant="fp8"), want)))
            for f in a.fault:
                rows.append((f, compare.train_readings(
                    wl, ref.replay(cfg, wl, seed, fault=f), want)))
        t2 = time.perf_counter()
        del sess
        for kind, r in rows:
            print(json.dumps({"workload": a.workload, "seed": seed,
                              "kind": kind, "readings": r,
                              "setup_s": t1 - t0, "check_s": t2 - t1,
                              "device": devices["kind"]}), flush=True)
        del want
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
