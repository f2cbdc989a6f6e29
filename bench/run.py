#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name:

* ``bench/workloads/<workload>.json`` — the traffic mix: its configuration,
  its driver and the driver's parameters;
* ``bench/configs/<config>.json`` — the configuration's published sizes,
  with its plain reference (``<config>.ref.py``) and its work count
  (``<config>.work.py``) beside it;
* ``bench/drivers/<driver>.py`` — how the cell's path is set up, driven
  for ``--seconds`` and checked against the reference;
* ``bench/metrics/<metric>.py`` — one reader per per-layer metric of
  ``BENCHMARK.json``, run in ``--trace 1`` runs of the cells it lists.

The run refuses anything but a chip: when JAX finds no TPU, fewer chips
than the cell asks for, or a ``device_kind`` that ``bench/peaks.json``
does not list, it exits with code 2 and prints no result.  It sets JAX's
persistent compilation cache to ``<checkout>/.jax_cache`` (a fixed path)
before JAX is imported.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and last ``checks``: each number compared with the
reference beside its limit.  The same checks are the last lines of
standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

from files import BENCH, load_json, module  # noqa: E402

ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class NoChip(Exception):
    """The run cannot measure here: no accelerator, or not the one asked for."""


def benchmark() -> dict:
    """``BENCHMARK.json`` at the root of the checkout."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name: str) -> tuple[dict, dict]:
    """(workload, configuration) of the cell ``name``."""
    wl = load_json("workloads", f"{name}.json")
    cfg = load_json("configs", f"{wl['config']}.json")
    return wl, cfg


def device_info(chips: int) -> dict:
    """The devices as JAX reports them; raises :class:`NoChip` off a chip."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"{len(devs)} chip(s), the cell asks for {chips}")
    kind = devs[0].device_kind
    if kind not in load_json("peaks.json"):
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return {"platform": devs[0].platform, "kind": kind, "count": chips}


def enable_cache() -> None:
    """Persistent compilation cache at the fixed in-checkout directory."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileCounter:
    """Counts lowerings (jit cache misses) while ``active``."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if self.active and event == self.EVENT:
            self.count += 1


class GcTimer:
    """Collections of Python's garbage collector, and their time, while
    ``active``: a pause that the window's clock would otherwise hide."""

    def __init__(self):
        self.active = False
        self.count = self.oldest = 0
        self.seconds = 0.0
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if not self.active:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.seconds += time.perf_counter() - self._t0
            self.count += 1
            self.oldest += info["generation"] == 2
            self._t0 = None


def peak_bytes(devices) -> int | None:
    """``peak_bytes_in_use`` of the fullest chip, where the backend says."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def window_line(win: dict, compiles: CompileCounter, pauses: GcTimer) -> str:
    """What the window held besides the cell's work, for standard error:
    its longest call (by phase, where the driver times phases), the
    compiles and the garbage collector's pauses inside it."""
    i = int(np.argmax(win["call_s"]))
    parts = win.get("call_parts")
    phases = "".join(f", {k} {v!r} s" for k, v in parts[i].items()) \
        if parts else ""
    return (f"window: {len(win['call_s'])} calls in {win['elapsed']!r} s, "
            f"longest {win['call_s'][i]!r} s (call {i}{phases}), "
            f"{compiles.count} compiles, {pauses.count} gc collections "
            f"({pauses.oldest} of the oldest generation) taking "
            f"{pauses.seconds!r} s")


def per_layer(bench: dict, name: str, ctx: dict) -> dict:
    """Each per-layer metric that lists this cell, read by its reader.

    A reader that finds nothing returns None and the metric is left out.
    """
    out = {}
    for m in bench["per_layer"]:
        if name not in m.get("workloads", [name]):
            continue
        value = module("metrics", f"{m['name']}.py").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             devices=None, driver=None, files=None) -> dict:
    """Set up, drive and check one cell; returns the result object.

    ``devices`` is what :func:`device_info` found.  Tests pass None, and
    may pass a ``driver`` and the cell's ``files`` (workload,
    configuration) of their own, to run the rest of a cell on the CPU.
    """
    import jax
    wl, cfg = files or cell(name)
    drv = driver or module("drivers", f"{wl['driver']}.py")
    compiles, pauses = CompileCounter(), GcTimer()
    sess = drv.setup(wl, cfg, seed)
    setup_s = time.perf_counter() - T_START
    compiles.active = pauses.active = True
    win = sess.window(seconds)
    print(window_line(win, compiles, pauses), file=sys.stderr)
    trace_dir = os.path.join(ROOT, ".bench_trace", name)
    tr = sess.traced(trace_dir, wl["trace_seconds"]) if trace else None
    compiles.active = pauses.active = False
    gc.callbacks.remove(pauses._on)
    used = jax.devices()[:wl["chips"]]
    peak = peak_bytes(used)
    checks = sess.check()
    del sess
    gc.collect()
    correct = bool(checks) and all(v <= lim for v, lim in checks.values())
    device = dict(devices or {"platform": used[0].platform,
                              "kind": used[0].device_kind,
                              "count": len(used)})
    device["memory_peak_bytes"] = peak
    result = {"correct": correct, "attempted": win["attempted"],
              "failed": win["failed"]}
    if trace:
        ctx = {"workload": wl, "config": cfg, "window": win, "trace": tr,
               "compiles_in_window": compiles.count, "peak_bytes": peak,
               "peaks": load_json("peaks.json")[device["kind"]]
               if devices else None,
               "work": module("configs", f"{wl['config']}.work.py")}
        result["metrics"] = per_layer(benchmark(), name, ctx)
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
    else:
        result["metrics"] = {"setup_s": {"value": setup_s, "unit": "s"},
                             **win["metrics"]}
    result["device"] = device
    if trace:
        result["breakdown"] = tr["breakdown"]
    result["checks"] = {k: [v, lim] for k, (v, lim) in checks.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        wl, _ = cell(a.workload)
    except FileNotFoundError as e:
        print(f"bench: unknown workload {a.workload!r} ({e})", file=sys.stderr)
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"bench: the program is not in this checkout ({e})",
              file=sys.stderr)
        return 2
    try:
        devices = device_info(wl["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    enable_cache()
    result = run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                      devices=devices)
    print(f"correct: {result['correct']}", file=sys.stderr)
    for k, (v, lim) in result["checks"].items():
        print(f"check {k}: {v!r} (limit {lim!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
