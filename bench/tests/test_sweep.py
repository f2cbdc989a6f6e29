"""The sweep cells' reference, work count, control and faults, at a fleet
a CPU test holds (64 nodes, 16 parameters, 2 virtual seconds: 100 ticks),
on the program's jnp tick (``ref``) and its Pallas kernel interpreted."""
import jax
import numpy as np
import pytest

import compare
import run

NINE, CLASSIC = "sweep.paper-fleet.nine", "sweep.paper-fleet.classic"
drv = run.module("drivers", "sweep.py")
ref = run.module("configs", "psp-paper-fleet.ref.py")
work = run.module("configs", "psp-paper-fleet.work.py")


def _cell(name, impl="ref", P=64, duration=2.0):
    wl, cfg = run.cell(name)
    return dict(wl, tick_impl=impl), dict(cfg, n_nodes=P, dim=16,
                                           duration=duration)


@pytest.fixture(autouse=True)
def _fresh_programs():
    from repro.core import vector_sim_jax
    vector_sim_jax._compiled_chunk.cache_clear()
    jax.clear_caches()
    yield
    vector_sim_jax._compiled_chunk.cache_clear()
    jax.clear_caches()


@pytest.mark.parametrize("name,impl,P", [(NINE, "ref", 64),
                                         (CLASSIC, "ref", 64),
                                         (NINE, "interpret", 16)])
def test_reference_agrees_with_the_sweep(name, impl, P):
    wl, cfg = _cell(name, impl, P, 1.0 if impl == "interpret" else 2.0)
    got = drv.setup(wl, cfg, 2 ** 33 + 1).first
    want = ref.replay(cfg, wl, got["seeds"])
    r = compare.sweep_readings(got, want)
    assert r["steps_mismatch"] == r["updates_mismatch"] == 0
    assert r["control_mismatch"] == 0
    assert r["error_gap"] < 2e-7
    assert np.all(want["updates"] > 0)


def test_lower_precision_is_not_correct():
    # the chip's control is Precision.HIGH, which the CPU computes in
    # float32; here the data plane's operands are rounded to bfloat16
    wl, cfg = _cell(NINE)
    seeds = drv.setup(wl, cfg, 5).seeds(0)
    want = ref.replay(cfg, wl, seeds)
    got = ref.replay(cfg, wl, seeds, "bfloat16")
    checks = compare.checks(wl, compare.sweep_readings(got, want))
    assert not all(v <= lim for v, lim in checks.values()), checks


def _broken(fault):
    from repro.kernels import ops
    real = ops.psp_tick

    def tick(state, rand, params, t, leave_n, join_n, **kw):
        if fault == "half_batch":
            m = rand["X"].shape[1] // 2
            rand = dict(rand, X=rand["X"][:, :m], mb=rand["mb"][:, :m])
        new, out = real(state, rand, params, t, leave_n, join_n, **kw)
        if fault == "unchanged":
            new = dict(state)
        elif fault == "answer":
            new = dict(new, steps=new["steps"].at[0, 0].add(1))
        return new, out
    return tick


@pytest.mark.parametrize("name", [NINE, CLASSIC])
def test_sound_run_is_correct(name):
    wl, cfg = _cell(name)
    r = run.run_cell(name, 3, 0.5, False, files=(wl, cfg), driver=drv)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["metrics"]["node_steps_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "answer"])
def test_fault_is_not_correct(monkeypatch, fault):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "psp_tick", _broken(fault))
    wl, cfg = _cell(NINE)
    r = run.run_cell(NINE, 3, 0.5, False, files=(wl, cfg), driver=drv)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("name", [NINE, CLASSIC])
def test_every_seed_draws_the_same_sweeps(name):
    """The seed orders the workload's pool of sweeps and draws no others,
    so a window's node-steps do not move with the seed."""
    wl, _ = run.cell(name)
    n = wl["pool"]["sweeps"]

    def pool(seed):
        return [tuple(drv.row_seeds(wl, seed, k)) for k in range(n)]

    a, b = pool(3), pool(2 ** 33 + 5)
    assert len(set(a)) == n and sorted(a) == sorted(b) and a != b
    assert pool(3) == a
    assert drv.row_seeds(wl, 3, n) == list(a[0])
    assert all(len(s) == len(ref.rows(wl)) for s in a)


def test_work_count_by_hand():
    _, cfg = _cell(NINE, P=64)
    # m = 8, d = 16: a push reads 8 x 17 + 16 floats, a pull writes 16;
    # each of 100 ticks reads 64 event times in each of 9 rows
    assert work.tick_bytes(cfg, rows=9, ticks=100, pushes=1000) == \
        1000 * 4 * (8 * 17 + 16 + 16) + 100 * 9 * 64 * 4
    assert work.tick_flops(cfg, pushes=1000) == 1000 * 4 * 8 * 16
    _, paper = run.cell(NINE)
    # at the paper's size a push and its pull move 40,032 bytes for
    # 32,000 FLOPs: bound by bytes on a v5e (819 GB/s, 197 TFLOP/s)
    assert work.tick_bytes(paper, 1, 0, 1) == 40_032
    assert work.tick_flops(paper, 1) / 197e12 < 40_032 / 819e9


def test_stride_matches_the_planner():
    from repro.core.sweep_plan import plan_sweep
    for name, P, d in [(NINE, 64, 16), (NINE, 1000, 1000), (CLASSIC, 100, 32)]:
        wl, cfg = _cell(name, P=P)
        cfg = dict(cfg, dim=d, duration=40.0)
        b = ref.batch(cfg, wl, list(range(len(ref.rows(wl)))))
        plan = plan_sweep(b["ticks"].size, b["measure"], len(ref.rows(wl)),
                          P, batch=cfg["batch"], d=d, k_max=b["k_max"],
                          masked=False, has_churn=False, mesh=(1, 1))
        assert b["stride"] == plan.stride, (name, P, d)


@pytest.mark.parametrize("name", [NINE, CLASSIC])
def test_paper_stride_does_not_depend_on_row_padding(monkeypatch, name):
    """At the paper's size one tick's noise fills the budget, so the
    stride is 1 however many rows the engine pads to: a data plane that
    runs the 9 real rows alone draws every number where it was."""
    wl, cfg = run.cell(name)
    seeds = list(range(len(ref.rows(wl))))
    strides = set()
    for block in (1, 9, 16, 32):
        monkeypatch.setattr(ref, "ROW_BLOCK", block)
        strides.add(ref.batch(cfg, wl, seeds)["stride"])
    assert strides == {1}
