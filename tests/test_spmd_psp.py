"""SPMD PSP trainer: one jittable program covering all five barriers.

Includes the elastic-worker-set (churn) coverage: population bounds,
convergence under churn, single-trace jit compilation with the churn
phase compiled in, and a committed golden churn trace
(``tests/golden/spmd_churn_trace.json`` — regenerate by running this
file with ``PSP_REGEN_GOLDEN=1``).  The cross-layer trainer↔simulator
churn equivalence lives in ``tests/test_elastic_equiv.py``.
"""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import env
from repro.core.spmd_psp import (ChurnConfig, PSPConfig, elastic_drive,
                                 linear_psp_state, linear_psp_task,
                                 make_psp_step_fn, psp_apply_tick, psp_init,
                                 psp_train_step)

D = 24

GOLDEN_CHURN = os.path.join(os.path.dirname(__file__), "golden",
                            "spmd_churn_trace.json")


@pytest.fixture(scope="module")
def task():
    return linear_psp_task(D)


def run(task, barrier, ticks=500, straggler_frac=0.25, workers=8):
    w_true, grad_fn, opt_update = task
    cfg = PSPConfig(barrier=barrier, n_workers=workers, sample_size=2,
                    staleness=3, straggler_frac=straggler_frac)
    st = psp_init(cfg, {"w": jnp.zeros((D,))}, lambda p: None,
                  jax.random.PRNGKey(1))
    step = jax.jit(lambda s, b: psp_train_step(cfg, grad_fn, opt_update,
                                               s, b))
    kb = jax.random.PRNGKey(2)
    for _ in range(ticks):
        kb, k1 = jax.random.split(kb)
        x = jax.random.normal(k1, (workers, 16, D))
        st, m = step(st, (x, x @ w_true))
    err = float(jnp.linalg.norm(st.server_params["w"] - w_true)
                / jnp.linalg.norm(w_true))
    return st, m, err


@pytest.fixture(scope="module")
def results(task):
    return {b: run(task, b) for b in ("bsp", "ssp", "asp", "pbsp", "pssp")}


def test_all_barriers_converge(results):
    for name, (st, m, err) in results.items():
        assert err < 0.25, (name, err)


def test_throughput_ordering(results):
    # steps per virtual second: BSP < SSP < {pBSP,pSSP} < ASP under stragglers
    thr = {k: float(m["mean_step"] / m["virtual_time"])
           for k, (st, m, e) in results.items()}
    assert thr["bsp"] < thr["ssp"] < thr["pbsp"] <= thr["asp"] * 1.05
    assert thr["pssp"] > thr["ssp"]


def test_spread_ordering(results):
    spread = {k: int(m["step_spread"]) for k, (st, m, e) in results.items()}
    assert spread["bsp"] <= 1
    assert spread["ssp"] <= 4
    assert spread["asp"] >= spread["pssp"]


def test_step_counters_and_pushes(results):
    st, m, _ = results["pbsp"]
    assert int(st.total_pushes) > 0
    assert int(st.step.max()) > 0


def test_read_my_writes_views_update(task):
    """With zero heterogeneity, BSP workers complete/pull in lockstep, so
    every worker's view is the SAME server snapshot (read-my-writes)."""
    w_true, grad_fn, opt_update = task
    cfg = PSPConfig(barrier="bsp", n_workers=4, sample_size=2,
                    compute_jitter=0.0, straggler_frac=0.0)
    st = psp_init(cfg, {"w": jnp.zeros((D,))}, lambda p: None,
                  jax.random.PRNGKey(1))
    step = jax.jit(lambda s, b: psp_train_step(cfg, grad_fn, opt_update,
                                               s, b))
    kb = jax.random.PRNGKey(2)
    for _ in range(20):
        kb, k1 = jax.random.split(kb)
        x = jax.random.normal(k1, (4, 16, D))
        st, m = step(st, (x, x @ w_true))
    views = st.views["w"]
    assert float(jnp.abs(views).max()) > 0          # pulls happened
    assert int(m["step_spread"]) == 0               # true lockstep
    assert bool(jnp.allclose(views, views[0][None], atol=1e-6))


def run_churn(task, barrier, ticks=300, workers=8,
              churn=ChurnConfig(leave_rate=1.5, join_rate=1.5,
                                horizon=40.0, seed=7)):
    """Drive the elastic trainer, returning per-tick alive/step traces."""
    del task  # the shared elastic_drive harness owns the task draw
    cfg = PSPConfig(barrier=barrier, n_workers=workers, sample_size=2,
                    staleness=3, straggler_frac=0.25, churn=churn)
    w_true, it = elastic_drive(cfg, D, ticks)
    alive_trace, now_trace, mean_step_trace = [], [], []
    for st, m in it:
        bits = np.packbits(np.asarray(st.alive)).tobytes()
        alive_trace.append(int.from_bytes(bits, "big"))  # any worker count
        now_trace.append(float(st.now))
        mean_step_trace.append(float(m["mean_step"]))
    err = float(jnp.linalg.norm(st.server_params["w"] - w_true)
                / jnp.linalg.norm(w_true))
    return st, dict(alive=alive_trace, now=now_trace,
                    mean_step=mean_step_trace), err


class TestElasticChurn:
    """Elastic worker sets: the trainer under Poisson leave/join churn."""

    @pytest.fixture(scope="class")
    def churn_run(self, task):
        return run_churn(task, "pssp")

    def test_population_bounds_and_actual_churn(self, churn_run):
        st, trace, _ = churn_run
        counts = [bin(a).count("1") for a in trace["alive"]]
        assert min(counts) >= 2 and max(counts) <= 8
        assert len(np.asarray(st.alive)) == 8  # bitmask covers all workers
        assert len(set(trace["alive"])) > 2          # membership really moved
        assert int(st.leave_cursor) >= 2 and int(st.join_cursor) >= 2

    def test_converges_under_churn(self, churn_run):
        _, trace, err = churn_run
        assert err < 0.25, err
        # alive-masked progress is monotone-ish and positive
        assert trace["mean_step"][-1] > trace["mean_step"][0]

    def test_virtual_time_always_advances(self, churn_run):
        _, trace, _ = churn_run
        nows = np.asarray(trace["now"])
        assert np.all(np.diff(nows) >= 0) and nows[-1] > nows[0]

    def test_golden_churn_trace(self, churn_run):
        """Fixed-seed elastic run pinned to the committed golden trace —
        any drift in churn ordering, RNG consumption, or alive-masked
        barrier decisions flips the integer alive bitmasks."""
        st, trace, err = churn_run
        got = {
            "alive_bitmask": trace["alive"][:120],
            "final_now": round(trace["now"][-1], 4),
            "leave_cursor": int(st.leave_cursor),
            "join_cursor": int(st.join_cursor),
            "total_pushes": int(st.total_pushes),
            "final_error": round(err, 5),
        }
        if env.flag("PSP_REGEN_GOLDEN"):
            with open(GOLDEN_CHURN, "w") as f:
                json.dump(got, f, indent=1)
        with open(GOLDEN_CHURN) as f:
            golden = json.load(f)
        assert got["alive_bitmask"] == golden["alive_bitmask"]
        assert got["leave_cursor"] == golden["leave_cursor"]
        assert got["join_cursor"] == golden["join_cursor"]
        assert got["total_pushes"] == golden["total_pushes"]
        assert abs(got["final_now"] - golden["final_now"]) < 1e-3
        assert abs(got["final_error"] - golden["final_error"]) < 1e-3

    def test_churn_jit_single_compilation(self, task):
        """The churn phase is lax-only: one trace, even as events fire."""
        w_true, grad_fn, opt_update = task
        cfg = PSPConfig(barrier="pbsp", n_workers=4, sample_size=2,
                        churn=ChurnConfig(leave_rate=3.0, join_rate=3.0,
                                          horizon=10.0, seed=1))
        st = psp_init(cfg, {"w": jnp.zeros((D,))}, lambda p: None,
                      jax.random.PRNGKey(0))
        calls = 0

        def counting(s, b):
            nonlocal calls
            calls += 1
            return psp_train_step(cfg, grad_fn, opt_update, s, b)

        step = jax.jit(counting)
        x = jnp.ones((4, 8, D))
        for _ in range(30):
            st, _ = step(st, (x, jnp.ones((4, 8))))
        assert calls == 1
        assert int(st.leave_cursor) + int(st.join_cursor) > 0


class TestAdaptivePolicies:
    """Adaptive barrier policies threaded through the trainer: the policy
    state rides in ``PSPState.policy``, pinned ranges reduce bit-for-bit
    to the static parents, and ``contribution="mean-alive"`` co-locates
    its churn-aware denominator EMA in the same pytree."""

    @staticmethod
    def _traj(cfg, ticks=60, dim=8):
        w_true, it = elastic_drive(cfg, dim, ticks)
        out = []
        for st, m in it:
            out.append((np.asarray(st.server_params["w"]),
                        np.asarray(st.step), float(st.now),
                        np.asarray(st.key)))
        return out, st

    BASE = dict(n_workers=6, straggler_frac=0.3)
    PAIRS = [
        (dict(barrier="dssp", staleness=3, staleness_lo=3),
         dict(barrier="ssp", staleness=3)),
        (dict(barrier="ebsp", max_advance=0), dict(barrier="bsp")),
        (dict(barrier="apssp", staleness=3, sample_size=3,
              sample_size_lo=3),
         dict(barrier="pssp", staleness=3, sample_size=3)),
    ]

    @pytest.mark.parametrize("i", range(3))
    def test_pinned_range_reduces_to_static_parent(self, i):
        akw, skw = self.PAIRS[i]
        ta, _ = self._traj(PSPConfig(**akw, **self.BASE))
        tb, _ = self._traj(PSPConfig(**skw, **self.BASE))
        for (wa, sa, na, ka), (wb, sb, nb, kb) in zip(ta, tb):
            np.testing.assert_array_equal(wa, wb)
            np.testing.assert_array_equal(sa, sb)
            assert na == nb
            np.testing.assert_array_equal(ka, kb)

    @pytest.mark.parametrize("barrier", ("dssp", "ebsp", "apbsp", "apssp"))
    def test_adaptive_policies_converge(self, barrier):
        cfg = PSPConfig(barrier=barrier, staleness=3, sample_size=2,
                        staleness_lo=0, sample_size_lo=1, max_advance=3,
                        **self.BASE)
        _, st = self._traj(cfg, ticks=200, dim=8)
        w_true, _, _ = linear_psp_task(8)
        err = float(jnp.linalg.norm(st.server_params["w"] - w_true)
                    / jnp.linalg.norm(w_true))
        assert err < 0.3, (barrier, err)
        assert st.policy                      # stateful policy carried
        assert int(st.total_pushes) > 0

    def test_policy_state_evolves(self):
        cfg = PSPConfig(barrier="ebsp", max_advance=3, **self.BASE)
        _, st = self._traj(cfg, ticks=50)
        ema = np.asarray(st.policy["ema"])
        assert ema.shape == (6,) and np.all(ema > 0)
        # stragglers' duration EMA must exceed the fast workers'
        slow = np.asarray(st.slow)
        assert ema[slow].min() > ema[~slow].max()

    def test_static_policy_state_is_empty(self):
        cfg = PSPConfig(barrier="pssp", **self.BASE)
        _, st = self._traj(cfg, ticks=5)
        assert st.policy == {}

    def test_mean_alive_contribution_tracks_population(self):
        cfg = PSPConfig(barrier="pssp", contribution="mean-alive",
                        churn=ChurnConfig(leave_rate=2.0, join_rate=0.2,
                                          horizon=30.0, seed=3),
                        **self.BASE)
        _, st = self._traj(cfg, ticks=150)
        denom = float(st.policy["denom"])
        n_alive = int(np.asarray(st.alive).sum())
        assert 1.0 <= denom <= 6.0
        assert denom < 6.0                    # EMA followed the leaves
        assert abs(denom - n_alive) < 3.0
        w_true, _, _ = linear_psp_task(8)
        err = float(jnp.linalg.norm(st.server_params["w"] - w_true)
                    / jnp.linalg.norm(w_true))
        assert err < 0.4, err

    def test_adaptive_jit_single_compilation(self, task):
        w_true, grad_fn, opt_update = task
        cfg = PSPConfig(barrier="dssp", staleness=3, n_workers=4)
        st = psp_init(cfg, {"w": jnp.zeros((D,))}, lambda p: None,
                      jax.random.PRNGKey(0))
        calls = 0

        def counting(s, b):
            nonlocal calls
            calls += 1
            return psp_train_step(cfg, grad_fn, opt_update, s, b)

        step = jax.jit(counting)
        x = jnp.ones((4, 8, D))
        for _ in range(10):
            st, _ = step(st, (x, jnp.ones((4, 8))))
        assert calls == 1
        assert int(st.policy["thr"]) <= 3


def test_jit_single_compilation(task):
    w_true, grad_fn, opt_update = task
    cfg = PSPConfig(barrier="pssp", n_workers=4, sample_size=2)
    st = psp_init(cfg, {"w": jnp.zeros((D,))}, lambda p: None,
                  jax.random.PRNGKey(0))
    calls = 0

    def counting(s, b):
        nonlocal calls
        calls += 1
        return psp_train_step(cfg, grad_fn, opt_update, s, b)

    step = jax.jit(counting)
    x = jnp.ones((4, 8, D))
    for _ in range(4):
        st, _ = step(st, (x, jnp.ones((4, 8))))
    assert calls == 1   # traced once — fully jittable barrier logic


# --------------------------------------------------------------------------- #
# the gated tick: the backward pass, the server update and the pull run only
# on ticks where some worker pushes or pulls
# --------------------------------------------------------------------------- #
#: the pBSP benchmark cell's barrier and virtual clock
#: (``bench/workloads/train.qwen2-0.5b.pbsp.json``, ``psp_seed`` 1)
PBSP_CELL = dict(barrier="pbsp", n_workers=2, sample_size=1, staleness=3,
                 straggler_frac=0.5, straggler_slowdown=4.0,
                 base_compute=0.1, compute_jitter=0.5, poll_interval=0.02)


def _select_cond(pred, true_fun, false_fun, *operands):
    """``lax.cond`` as a select: both branches run and the predicate picks
    one result."""
    return jax.tree.map(lambda t, f: jnp.where(pred, t, f),
                        true_fun(*operands), false_fun(*operands))


def _hlo_computations(text: str) -> tuple[dict, str]:
    """``{name: body lines}`` of an HLO module's text, and the entry's name."""
    comps, cur, entry = {}, None, None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            m = re.match(r"(ENTRY )?%?([\w.\-]+) ", line)
            if m:
                cur = m.group(2)
                comps[cur] = []
                entry = cur if m.group(1) else entry
                continue
        if cur is not None:
            comps[cur].append(line)
    return comps, entry


def _reachable(comps: dict, root: str) -> set:
    """``root`` and every computation it calls, fusions and loops included."""
    seen, todo = set(), [root]
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for line in comps[c]:
            for m in re.finditer(r"(?:calls|to_apply|body|condition|"
                                 r"branch_computations)=\{?([^}\s]+(?:, "
                                 r"%[\w.\-]+)*)", line):
                todo += [n for n in re.findall(r"%?([\w.\-]+)", m.group(1))
                         if n in comps]
    return seen


class TestGatedTick:
    """``psp_apply_tick`` decides from the tick's push and pull masks which
    phases run, with the same numbers as running every phase every tick."""

    TICKS = 400

    def test_gated_step_matches_ungated_constant_gradient_path(
            self, task, monkeypatch):
        w_true, grad_fn, opt_update = task
        cfg = PSPConfig(**PBSP_CELL)
        grads_of = jax.jit(jax.vmap(grad_fn))
        batches, gated = [], []
        step = jax.jit(make_psp_step_fn(cfg, grad_fn, opt_update))
        st = linear_psp_state(cfg, D, init_seed=1)
        kb = jax.random.PRNGKey(2)
        for _ in range(self.TICKS):
            kb, k1 = jax.random.split(kb)
            x = jax.random.normal(k1, (cfg.n_workers, 16, D))
            batches.append((x, x @ w_true))
            forward = float(jnp.mean(grads_of(st.views, batches[-1])[0]))
            st, m = step(st, batches[-1])
            gated.append((jax.device_get(st), jax.device_get(m), forward))

        # the update every tick: gradients computed outside the step for
        # both workers, and the server update selected after it ran (the
        # pull against the protocol: test_each_phase_follows_the_clock)
        monkeypatch.setattr(jax.lax, "cond", _select_cond)
        ungated = jax.jit(lambda s, losses, grads: psp_apply_tick(
            cfg, opt_update, s, lambda _: (losses, grads)))
        st = linear_psp_state(cfg, D, init_seed=1)
        kinds = set()
        for batch, (g_st, g_m, forward) in zip(batches, gated):
            st, m = ungated(st, *grads_of(st.views, batch))
            for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(g_st)):
                np.testing.assert_array_equal(np.asarray(a), b)
            assert int(m["pushes"]) == int(g_m["pushes"])
            assert float(m["loss"]) == float(g_m["loss"])
            if int(g_m["pushes"]) == 0:
                assert abs(float(g_m["loss"]) - forward) <= 1e-6
            kinds.add((int(g_m["pushes"]) > 0, int(g_m["allowed"]) > 0))
        assert kinds == {(False, False), (False, True), (True, False),
                         (True, True)}

    @pytest.mark.parametrize("clock,at_once", [
        (PBSP_CELL, 1),
        # a 4x straggler holds three fast workers at the barrier, which
        # then all pull at once
        (dict(barrier="bsp", n_workers=4, compute_jitter=0.0,
              straggler_frac=0.25), 3),
    ], ids=["pbsp-cell", "bsp-straggler"])
    def test_each_phase_follows_the_clock(self, task, clock, at_once):
        """Against the protocol itself, tick by tick: the server moves by
        the pushing workers' mean gradient exactly on ticks where the
        clock says some worker pushes, and a worker's view becomes the new
        server model exactly when its step advances."""
        w_true, grad_fn, opt_update = task
        cfg = PSPConfig(**clock)
        grads_of = jax.jit(jax.vmap(grad_fn))
        step = jax.jit(make_psp_step_fn(cfg, grad_fn, opt_update))
        st = linear_psp_state(cfg, D, init_seed=1)
        kb = jax.random.PRNGKey(2)
        most = 0
        for _ in range(self.TICKS):
            kb, k1 = jax.random.split(kb)
            x = jax.random.normal(k1, (cfg.n_workers, 16, D))
            old = jax.device_get(st)
            push = (old.busy_until <= old.now) & ~old.pushed & old.alive
            g = np.asarray(grads_of(st.views, (x, x @ w_true))[1]["w"])
            st, m = step(st, (x, x @ w_true))
            new = jax.device_get(st)
            assert int(m["pushes"]) == push.sum()
            if push.any():
                want = old.server_params["w"] - 0.1 * g[push].mean(axis=0)
                np.testing.assert_allclose(new.server_params["w"], want,
                                           rtol=0, atol=1e-6)
                assert not np.array_equal(new.server_params["w"],
                                          old.server_params["w"])
            else:
                np.testing.assert_array_equal(new.server_params["w"],
                                              old.server_params["w"])
            pulled = new.step > old.step
            most = max(most, pulled.sum())
            for w in range(cfg.n_workers):
                np.testing.assert_array_equal(
                    new.views["w"][w], new.server_params["w"] if pulled[w]
                    else old.views["w"][w])
        assert most == at_once

    def test_counters_follow_the_masks(self):
        cfg = PSPConfig(**PBSP_CELL)
        _, it = elastic_drive(cfg, 8, 3000)
        rows = np.array([[int(m[k]) for k in ("updated", "pushes", "pulled",
                                              "allowed")] for _, m in it])
        updated, pushes, pulled, allowed = rows.T
        np.testing.assert_array_equal(updated, pushes > 0)
        np.testing.assert_array_equal(pulled, allowed > 0)
        # the cell's clock: ≈ 12% of ticks update the server, ≈ 12% pull,
        # and a push lands on 5.9% of worker-ticks (`landed_fraction.train`)
        assert round(updated.mean(), 4) == 0.1187
        assert round(pulled.mean(), 4) == 0.1183
        assert round(pushes.sum() / (2 * 3000), 4) == 0.0593

    def test_poll_branch_compiles_without_the_backward_pass(self):
        from repro.configs import get_config, reduced
        from repro.launch.train import make_psp_step
        from repro.models import init_model
        from repro.optim import adamw, warmup_cosine
        cfg = reduced(get_config("qwen2-0.5b"), n_layers=2, d_model=64)
        opt = adamw(warmup_cosine(3e-3, 1, 3))
        pcfg = PSPConfig(**PBSP_CELL)
        state = jax.eval_shape(
            lambda k: psp_init(pcfg, init_model(cfg, k), opt.init, k),
            jax.random.PRNGKey(0))
        toks = jax.ShapeDtypeStruct((2, 2, 16), jnp.int32)
        comps, entry = _hlo_computations(
            make_psp_step(cfg, pcfg, opt).lower(state, toks).compile()
            .as_text())

        def products(root):
            return sum(bool(re.search(r" (dot|convolution)\(", line))
                       for c in _reachable(comps, root) for line in comps[c])

        conds = [re.findall(r"%([\w.\-]+)", m.group(1))
                 for line in comps[entry]
                 for m in [re.search(r"branch_computations=\{([^}]*)\}",
                                     line)] if m]
        # lax.cond lists the false branch first: hold, then update
        (hold, update), = [b for b in conds if sum(map(products, b))]
        assert 0 < products(hold) < products(update) / 2

        # nothing the size of a parameter (or of a worker's views) is copied
        # outside the branches, on every tick
        sizes = {int(np.prod(p.shape)) * k
                 for p in jax.tree.leaves(state.server_params)
                 if p.size >= 1024 for k in (1, pcfg.n_workers)}
        inside = set().union(*(_reachable(comps, b) for bs in conds
                               for b in bs))
        copied = [int(np.prod([int(d) for d in m.group(1).split(",") if d]))
                  for c in _reachable(comps, entry) - inside
                  for line in comps[c]
                  for m in [re.search(r"= f32\[([\d,]*)\]\S* copy\(", line)]
                  if m]
        assert not sizes.intersection(copied)
