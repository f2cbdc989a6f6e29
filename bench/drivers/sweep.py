"""Sweep cells: ``repro.core.vector_sim.run_sweep(configs, backend="jax")``.

One sweep runs every row of the cell (a policy at a straggler fraction)
on the configuration's fleet, with the tick implementation the workload
names (``PSP_TICK_IMPL``) on a one-chip ``1x1`` mesh.  The rows' seeds
come from the workload's fixed pool of sweeps (``pool``), taken in an
order drawn from ``--seed`` (:func:`row_seeds`): every run draws from
the same sweeps, so the seed moves their order and not the work, and
every run repeats.  Set-up runs sweep 0, which compiles or loads every
chunk program the sweep's plan uses; the window then runs sweeps 1, 2,
... until ``--seconds`` have passed, each ended by ``run_sweep``
returning its results on the host.
"""
from __future__ import annotations

import os
import shutil
import sys
import time

import jax
import numpy as np

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

import files  # noqa: E402
import traffic  # noqa: E402


def sim_configs(cfg: dict, wl: dict, seeds: list[int]) -> list:
    """The program's ``SimConfig`` of each row, as the configuration states."""
    from repro.core.barriers import make_barrier
    from repro.core.simulator import SimConfig
    ref = _reference(wl)
    P = cfg["n_nodes"]
    return [SimConfig(
        n_nodes=P, duration=cfg["duration"], dim=cfg["dim"],
        batch=cfg["batch"], lr=cfg["lr_times_nodes"] / P,
        base_compute=cfg["base_compute"],
        compute_jitter=cfg["compute_jitter"], straggler_frac=frac,
        straggler_slowdown=cfg["straggler_slowdown"],
        barrier=make_barrier(name, **cfg["policies"][name]),
        distributed_sampling=cfg["distributed_sampling"],
        poll_interval=cfg["poll_interval"],
        measure_interval=cfg["measure_interval"],
        noise_std=cfg["noise_std"], seed=seed)
        for (name, frac), seed in zip(ref.rows(wl), seeds)]


def row_seeds(wl: dict, seed: int, k: int) -> list[int]:
    """Row seeds of sweep ``k`` of a run with ``seed``: entry
    ``order[k mod n]`` of the workload's pool of ``n`` sweeps, where
    ``order`` is a permutation of the pool drawn from ``seed``."""
    pool = wl["pool"]
    n = int(pool["sweeps"])
    order = np.random.default_rng(
        np.random.SeedSequence([int(seed), 3])).permutation(n)
    j = int(order[k % n])
    return [traffic.seed32(pool["seed"], j, r)
            for r in range(len(_reference(wl).rows(wl)))]


class Session:
    """The cell's sweeps: the warm-up, the window's, the traced ones."""

    def __init__(self, wl: dict, cfg: dict, seed: int):
        os.environ["PSP_TICK_IMPL"] = wl["tick_impl"]
        os.environ["PSP_SWEEP_MESH"] = "1x1"
        self.wl, self.cfg, self.seed = wl, cfg, seed
        self.n_rows = len(_reference(wl).rows(wl))
        self.k = 0
        self.done: list[dict] = []
        self.first = self._sweep()

    def seeds(self, k: int) -> list[int]:
        """Row seeds of sweep ``k``."""
        return row_seeds(self.wl, self.seed, k)

    def _sweep(self) -> dict:
        from repro.core.vector_sim import run_sweep
        seeds = self.seeds(self.k)
        res = run_sweep(sim_configs(self.cfg, self.wl, seeds), backend="jax")
        out = {"k": self.k, "seeds": seeds,
               "steps": np.stack([r.steps for r in res]),
               "updates": np.asarray([r.total_updates for r in res]),
               "control": np.asarray([r.control_messages for r in res]),
               "errors": np.stack([r.errors for r in res])}
        self.k += 1
        return out

    def _sweeps(self, seconds: float) -> dict:
        done, call_s = [], []
        t_first = t1 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.sweep"):
                done.append(self._sweep())
            t0, t1 = t1, time.perf_counter()
            call_s.append(t1 - t0)
            if t1 - t_first >= seconds:
                break
        return {"sweeps": done, "elapsed": t1 - t_first, "call_s": call_s,
                "node_steps": int(sum(d["steps"].sum() for d in done)),
                "updates": int(sum(d["updates"].sum() for d in done))}

    def window(self, seconds: float) -> dict:
        """Sweeps for ``seconds``; the end-to-end metrics and counters."""
        w = self._sweeps(seconds)
        self.done = w.pop("sweeps")
        w.update(attempted=len(self.done), failed=0,
                 ticks=len(self.done) * self._n_ticks(), rows=self.n_rows,
                 metrics={"node_steps_per_s": {
                     "value": w["node_steps"] / w["elapsed"],
                     "unit": "steps/s"}})
        return w

    def _n_ticks(self) -> int:
        return _reference(self.wl).batch(
            self.cfg, self.wl, self.seeds(0))["ticks"].size

    def traced(self, trace_dir: str, seconds: float) -> dict:
        """Further sweeps for ``seconds`` under the profiler, reduced."""
        trace = files.module("trace.py")
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        try:
            w = self._sweeps(seconds)
        finally:
            jax.profiler.stop_trace()
        try:
            tr = trace.reduce(trace.find_xplane(trace_dir), 1)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        tr["counts"] = {"sweeps": len(w["sweeps"]), "rows": self.n_rows,
                        "ticks": len(w["sweeps"]) * self._n_ticks(),
                        "pushes": w["updates"]}
        return tr

    def check(self) -> dict:
        """One window sweep drawn from the seed, against the reference;
        ``{name: (reading, limit)}``."""
        import compare
        pick = np.random.default_rng(
            np.random.SeedSequence([int(self.seed), 2])).integers(
                len(self.done))
        got = self.done[pick]
        self.done = []
        want = _reference(self.wl).replay(self.cfg, self.wl, got["seeds"])
        return compare.checks(self.wl, compare.sweep_readings(got, want))


def _reference(wl: dict):
    """The configuration's plain reference module."""
    return files.module("configs", f"{wl['config']}.ref.py")


def setup(wl: dict, cfg: dict, seed: int) -> Session:
    """The cell's session, set up through its warm-up sweep."""
    return Session(wl, cfg, seed)
