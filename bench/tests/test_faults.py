"""A run of a trainer cell with its timed path broken underneath must come
out not correct, and the control (the reference in float8 in the
program's place) must fail the comparison too.

Each run skips the look for a chip and drives the rest of a run through
:func:`run.run_cell` at the size in ``tiny.py``, in float32 so that the
sound run reads near zero.  The faults are planted in the program's step
builders, which the driver calls.
"""
import jax
import pytest

import compare
import run
import tiny

PBSP, SYNC = "train.qwen2-0.5b.pbsp", "train.qwen2-0.5b.sync"
drv = run.module("drivers", "train.py")
ref = run.module("configs", "qwen2-0.5b.ref.py")


def _broken_psp(fault):
    from repro.core.spmd_psp import psp_train_step
    from repro.models import loss_fn
    from repro.optim import clip_by_norm

    def make(cfg, pcfg, opt):
        def grad_fn(p, tokens):
            (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                p, {"tokens": tokens}, cfg)
            return loss, clip_by_norm(g, 1.0)

        def step(s, b):
            if fault == "half_batch":
                b = b[:, :b.shape[1] // 2]
            elif fault == "straggler_push":   # its completion never pushes
                s = s._replace(pushed=s.pushed | (s.slow & (
                    s.busy_until <= s.now)))
            new, m = psp_train_step(pcfg, grad_fn, opt.update, s, b)
            if fault == "unchanged":
                new = s
            elif fault == "barrier":
                new = new._replace(pushed=~new.pushed)
            return new, m
        return jax.jit(step)
    return make


def _broken_sync(fault):
    from repro.launch.steps import make_train_step

    def make(cfg, opt):
        real = make_train_step(cfg, opt)

        def step(params, opt_state, batch):
            if fault == "half_batch":
                t = batch["tokens"]
                batch = {"tokens": t[:t.shape[0] // 2]}
            p, o, loss, m = real(params, opt_state, batch)
            if fault == "unchanged":
                p, o = params, opt_state
            return p, o, loss, m
        return step
    return make


def _run(name, monkeypatch=None, fault=None):
    wl, cfg = tiny.cell(name)
    if fault:
        import repro.launch.steps
        import repro.launch.train
        if name == PBSP:
            monkeypatch.setattr(repro.launch.train, "make_psp_step",
                                _broken_psp(fault))
        else:
            monkeypatch.setattr(repro.launch.steps, "make_train_step",
                                _broken_sync(fault))
    return run.run_cell(name, 11, 0.3, False, files=(wl, cfg), driver=drv)


@pytest.mark.parametrize("name", [PBSP, SYNC])
def test_sound_run_is_correct(name):
    r = _run(name)
    assert r["correct"], r["checks"]
    assert list(r) == ["correct", "attempted", "failed", "metrics", "device",
                       "checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.mark.parametrize("name,fault", [
    (PBSP, "unchanged"), (PBSP, "half_batch"), (PBSP, "barrier"),
    (PBSP, "straggler_push"), (SYNC, "unchanged"), (SYNC, "half_batch")])
def test_fault_is_not_correct(monkeypatch, name, fault):
    r = _run(name, monkeypatch, fault)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("name", [PBSP, SYNC])
def test_control_is_not_correct(name):
    wl, cfg = tiny.control_cell(name)
    want = ref.replay(cfg, wl, 13)
    got = ref.replay(cfg, wl, 13, quant="fp8")
    checks = compare.checks(wl, compare.train_readings(wl, got, want))
    assert not all(v <= lim for v, lim in checks.values()), checks
