"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_tpu
from repro.kernels.rmsnorm import rmsnorm_tpu
from repro.kernels.ssd_scan import ssd_scan_tpu

KEY = jax.random.PRNGKey(0)


def tol(dtype):
    return dict(atol=5e-3, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=1e-4)


# --------------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,H,hd,window,cap", [
    (128, 2, 64, None, None),
    (256, 4, 64, None, None),
    (256, 2, 128, 64, None),
    (128, 2, 64, None, 30.0),
    (256, 1, 64, 128, 50.0),
    (384, 2, 32, None, None),       # non-pow2 sequence (3 blocks of 128)
])
def test_flash_vs_oracle(S, H, hd, window, cap, dtype):
    q = jax.random.normal(KEY, (2, S, H, hd), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (2, S, H, hd), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (2, S, H, hd), dtype)
    got = ops.attention(q, k, v, window=window, softcap=cap,
                        impl="interpret")
    want = ref.attention_ref(q, k, v, window=window, softcap=cap)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), **tol(dtype))


def test_flash_block_shapes_swept():
    q = jax.random.normal(KEY, (1, 4, 256, 64))
    for bq, bk in [(64, 64), (128, 64), (64, 128), (256, 256)]:
        got = flash_attention_tpu(q, q, q, causal=True, block_q=bq,
                                  block_k=bk, interpret=True)
        want = ref.attention_ref(q.transpose(0, 2, 1, 3),
                                 q.transpose(0, 2, 1, 3),
                                 q.transpose(0, 2, 1, 3))
        np.testing.assert_allclose(got.transpose(0, 2, 1, 3), want,
                                   atol=2e-5, rtol=1e-4)


def test_flash_tile_skipping_correct():
    """pl.when-skipped tiles must not corrupt the accumulation (SWA)."""
    q = jax.random.normal(KEY, (1, 1, 512, 64))
    got = flash_attention_tpu(q, q, q, causal=True, window=128,
                              block_q=128, block_k=128, interpret=True)
    want = ref.attention_ref(q.transpose(0, 2, 1, 3),
                             q.transpose(0, 2, 1, 3),
                             q.transpose(0, 2, 1, 3), window=128)
    np.testing.assert_allclose(got.transpose(0, 2, 1, 3), want,
                               atol=2e-5, rtol=1e-4)


# --------------------------------------------------------------------------- #
# SSD scan
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,hd,N,chunk", [
    (128, 64, 32, 64),
    (256, 64, 128, 128),
    (256, 32, 64, 64),
])
def test_ssd_vs_sequential_oracle(S, hd, N, chunk, dtype):
    BH = 4
    xdt = (jax.random.normal(KEY, (BH, S, hd)) * 0.5).astype(dtype)
    dA = (-jax.random.uniform(jax.random.fold_in(KEY, 3), (BH, S)) * 0.1
          ).astype(dtype)
    Bm = (jax.random.normal(jax.random.fold_in(KEY, 4), (BH, S, N)) * 0.3
          ).astype(dtype)
    Cm = (jax.random.normal(jax.random.fold_in(KEY, 5), (BH, S, N)) * 0.3
          ).astype(dtype)
    got = ssd_scan_tpu(xdt, dA, Bm, Cm, chunk=chunk, interpret=True)
    want = ops.ssd(xdt, dA, Bm, Cm, impl="cpu")
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32),
                               atol=2e-2 if dtype == jnp.bfloat16 else 1e-4,
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-3)


def test_ssd_chunk_invariance():
    """The chunked dual form must be invariant to the chunk size."""
    BH, S, hd, N = 2, 256, 32, 16
    xdt = jax.random.normal(KEY, (BH, S, hd)) * 0.5
    dA = -jax.random.uniform(jax.random.fold_in(KEY, 1), (BH, S)) * 0.2
    Bm = jax.random.normal(jax.random.fold_in(KEY, 2), (BH, S, N)) * 0.3
    Cm = jax.random.normal(jax.random.fold_in(KEY, 3), (BH, S, N)) * 0.3
    outs = [ssd_scan_tpu(xdt, dA, Bm, Cm, chunk=c, interpret=True)
            for c in (32, 64, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=1e-4, rtol=1e-3)


# --------------------------------------------------------------------------- #
# rmsnorm
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 128), (3, 100, 512), (2, 7, 896)])
def test_rmsnorm_vs_oracle(shape, dtype):
    x = jax.random.normal(KEY, shape, dtype)
    w = jax.random.normal(jax.random.fold_in(KEY, 6), (shape[-1],))
    got = rmsnorm_tpu(x, w, interpret=True)
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), **tol(dtype))


# --------------------------------------------------------------------------- #
# XLA flash path (models/flash.py custom VJP) vs oracle incl. gradients
# --------------------------------------------------------------------------- #
def test_xla_flash_custom_vjp_grads():
    from repro.models.flash import flash_attention
    q = jax.random.normal(KEY, (2, 256, 4, 32))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 256, 4, 32))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (2, 256, 4, 32))

    for window, cap in [(None, None), (64, None), (None, 30.0)]:
        f = lambda *a: jnp.sum(jnp.sin(
            flash_attention(*a, True, window, cap, 128, 128)))
        g = lambda *a: jnp.sum(jnp.sin(ref.attention_ref(
            *a, window=window, softcap=cap)))
        d1 = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        d2 = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(d1, d2):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-3)


# --------------------------------------------------------------------------- #
# psp_tick: fused sweep tick (control + data plane) vs its jnp reference
# --------------------------------------------------------------------------- #
def _tick_problem(seed, B, P, churn, ragged, k_max, d=5, m=4,
                  adaptive=False):
    """Random mid-flight tick state + params + one tick's noise.

    Row 0 gets a short horizon so the chained-tick tests cross the
    row-freeze gate (the merged-duration / dead-padding path) mid-run.
    With ``adaptive`` the batch mixes static rows with DSSP / Elastic-BSP
    / β-annealing rows carrying mid-flight policy state.
    """
    rng = np.random.default_rng(seed)
    n_true = np.full(B, P)
    if ragged:
        n_true = rng.integers(max(3, P // 2), P + 1, size=B)
        n_true[rng.integers(B)] = P          # batch width = max population
    valid_slot = np.arange(P) < n_true[:, None]
    alive = valid_slot & (rng.random((B, P)) < 0.85)
    alive[:, 0] = valid_slot[:, 0]           # keep every row populated
    kind = rng.integers(0, 3, size=B)        # 0=asp 1=full-view 2=sampled
    state = {
        "steps": rng.integers(0, 6, (B, P)).astype(np.int32),
        "alive": alive,
        "computing": rng.random((B, P)) < 0.5,
        "event_time": (rng.random((B, P)) * 2).astype(np.float32),
        "ready": (rng.random((B, P)) * 2).astype(np.float32),
        "blocked": rng.random((B, P)) < 0.3,
        "pend_leave": rng.integers(0, 2, B).astype(np.int32),
        "pend_join": rng.integers(0, 2, B).astype(np.int32),
        "w": rng.normal(size=(B, d)).astype(np.float32),
        "pulled": rng.normal(size=(B, P, d)).astype(np.float32),
    }
    horizon = np.full(B, 10.0, np.float32)
    horizon[0] = 0.5                         # row 0 freezes mid-run
    params = {
        "staleness": rng.integers(0, 4, B).astype(np.int32),
        "beta_clip": np.clip(k_max, 0, n_true - 1).astype(np.int32),
        "is_asp": kind == 0,
        "full_view": kind == 1,
        "sampled": kind == 2,
        "dist_hops": rng.integers(0, 5, B).astype(np.int32),
        "compute_time": (0.05 + rng.random((B, P)) * 0.1).astype(np.float32),
        "valid_slot": valid_slot,
        "w_true": rng.normal(size=(B, d)).astype(np.float32),
        "lr": (0.01 + rng.random(B) * 0.1).astype(np.float32),
        "noise_std": (rng.random(B) * 0.2).astype(np.float32),
        "horizon": horizon,
        "eps": np.float32(1e-4),
        "poll": np.float32(0.02),
    }
    masked = churn or ragged
    rand = {"dur": rng.random((B, P)).astype(np.float32),
            "X": rng.normal(size=(P, m, d)).astype(np.float32),
            "mb": rng.normal(size=(P, m)).astype(np.float32)}
    if k_max == 1 and not masked:
        rand["u1"] = rng.random(P).astype(np.float32)
    elif k_max > 0:
        shape = (B, P, P) if masked else (P, P)
        rand["scores"] = rng.random(shape).astype(np.float32)
    if churn:
        rand["leave"] = rng.random((B, P)).astype(np.float32)
        rand["join"] = rng.random((B, P)).astype(np.float32)
    leave_n = rng.integers(0, 2, B).astype(np.int32) * churn
    join_n = rng.integers(0, 2, B).astype(np.int32) * churn
    if adaptive:
        # draws appended last so static problems stay bit-identical
        akind = rng.integers(0, 4, size=B)   # 0=keep 1=dssp 2=ebsp 3=anneal
        is_dssp, is_ebsp = akind == 1, akind == 2
        is_ann = (akind == 3) & (k_max > 0)
        adapt = is_dssp | is_ebsp | is_ann
        params["is_dssp"], params["is_ebsp"] = is_dssp, is_ebsp
        params["is_anneal"] = is_ann
        params["full_view"] = np.where(adapt, is_dssp | is_ebsp,
                                       params["full_view"])
        params["sampled"] = np.where(adapt, is_ann, params["sampled"])
        params["is_asp"] = np.where(adapt, False, params["is_asp"])
        params["pol_lo"] = rng.integers(
            0, params["staleness"] + 1).astype(np.int32)
        params["beta_lo"] = rng.integers(
            0, params["beta_clip"] + 1).astype(np.int32)
        params["ebsp_range"] = (rng.random(B) * 4).astype(np.float32)
        params["ebsp_alpha"] = np.full(B, 0.5, np.float32)
        state["pol_thr"] = rng.integers(
            0, params["staleness"] + 1).astype(np.int32)
        state["pol_ema"] = (rng.random((B, P)) * 0.3).astype(np.float32)
        state["pol_beta"] = np.where(
            is_ann, params["beta_lo"], max(k_max, 0)).astype(np.int32)
    return state, rand, params, leave_n, join_n, masked


@pytest.mark.parametrize("churn,ragged,k_max,adaptive", [
    (False, False, 0, False),
    (False, False, 1, False),        # β = 1 fast path
    (False, False, 3, False),        # shared-score rank path
    (True, False, 2, False),         # churn: per-row masked scores
    (False, True, 2, False),         # ragged padding: dead-slot masking
    (True, True, 2, False),          # churn × ragged
    (False, False, 0, True),         # adaptive full-view (dssp/ebsp) rows
    (False, False, 3, True),         # adaptive incl. β-annealing rows
    (True, True, 2, True),           # adaptive × churn × ragged
])
def test_psp_tick_kernel_matches_ref(churn, ragged, k_max, adaptive):
    """Interpret-mode Pallas tick ≡ jnp reference, bit for bit, tick for
    tick — including the data-plane state (``w``/``pulled``) carried
    across several chained ticks, and the row-freeze (horizon) gate.

    Both paths run under jit, as in production (inside the sweep scan):
    eager-vs-compiled would differ by FMA-contraction ulps, jitted they
    must agree exactly.
    """
    import functools
    import jax
    from repro.kernels import ops as kops
    B, P = 3, 8
    state, rand, params, leave_n, join_n, masked = _tick_problem(
        0, B, P, churn, ragged, k_max, adaptive=adaptive)
    tick = {impl: jax.jit(functools.partial(
        kops.psp_tick, k_max=k_max, has_churn=churn, masked=masked,
        adaptive=adaptive, impl=impl)) for impl in ("ref", "interpret")}
    s_ref, s_ker = dict(state), dict(state)
    for i in range(5):
        t = np.float32(0.4 * (i + 1))
        rng_i = np.random.default_rng(100 + i)
        rand_i = {k: (rng_i.normal(size=v.shape) if k in ("X", "mb")
                      else rng_i.random(v.shape)).astype(np.float32)
                  for k, v in rand.items()}
        s_ref, o_ref = tick["ref"](s_ref, rand_i, params, t, leave_n,
                                   join_n)
        s_ker, o_ker = tick["interpret"](s_ker, rand_i, params, t, leave_n,
                                         join_n)
        for k in s_ref:
            np.testing.assert_array_equal(np.asarray(s_ref[k]),
                                          np.asarray(s_ker[k]),
                                          err_msg=f"tick {i} state {k}")
        for k in o_ref:
            np.testing.assert_array_equal(np.asarray(o_ref[k]),
                                          np.asarray(o_ker[k]),
                                          err_msg=f"tick {i} out {k}")


@pytest.mark.parametrize("adaptive", (False, True))
def test_psp_tick_frozen_row_is_inert(adaptive):
    """A row past its horizon must not move at all — state bit-frozen
    (including adaptive policy state), zero finishes, zero control
    traffic (the dead-padding-tick guarantee the chunk scheduler
    relies on)."""
    import functools
    import jax
    from repro.kernels import ops as kops
    B, P = 3, 8
    state, rand, params, leave_n, join_n, masked = _tick_problem(
        1, B, P, True, False, 2, adaptive=adaptive)
    params = dict(params)
    params["horizon"] = np.zeros(B, np.float32)      # all rows frozen
    leave_n = leave_n + 1                            # pending churn too
    tick = jax.jit(functools.partial(kops.psp_tick, k_max=2,
                                     has_churn=True, masked=masked,
                                     adaptive=adaptive, impl="ref"))
    new_state, out = tick(state, rand, params, np.float32(1.0),
                          leave_n, join_n)
    for k in state:
        np.testing.assert_array_equal(np.asarray(new_state[k]),
                                      np.asarray(state[k]),
                                      err_msg=f"state {k} moved")
    assert not np.asarray(out["fin"]).any()
    assert not np.asarray(out["start"]).any()
    assert np.asarray(out["n_fin"]).sum() == 0
    assert np.asarray(out["ctrl"]).sum() == 0


def test_psp_tick_interpret_reproduces_golden_sweep(monkeypatch):
    """A whole sweep through the interpret-mode kernel reproduces the jax
    backend's committed golden trace (β = 1 fast path scenario)."""
    import json
    import os
    from repro.core.simulator import SimConfig
    from repro.core.vector_sim import run_sweep
    from repro.core.barriers import make_barrier

    monkeypatch.setenv("PSP_TICK_IMPL", "interpret")
    cfg = SimConfig(n_nodes=3, duration=4.0, dim=4, batch=4, seed=11,
                    barrier=make_barrier("pbsp", staleness=2, sample_size=1))
    r = run_sweep([cfg], backend="jax")[0]
    golden_path = os.path.join(os.path.dirname(__file__), "golden",
                               "vector_sim_trace.json")
    with open(golden_path) as f:
        g = json.load(f)["jax"]
    assert r.steps.tolist() == g["steps"]
    assert r.total_updates == g["total_updates"]
    assert r.server_updates.tolist() == g["server_updates"]
    np.testing.assert_allclose(r.errors, g["errors"], rtol=1e-4, atol=1e-5)


def test_psp_tick_churn_sweep_impl_invariant(monkeypatch):
    """Churn sweeps agree exactly across tick impls (ref vs interpret)."""
    from repro.core.simulator import SimConfig
    from repro.core.vector_sim import run_sweep
    from repro.core.barriers import make_barrier

    cfgs = [SimConfig(n_nodes=10, duration=3.0, dim=4, batch=4, seed=s,
                      churn_leave_rate=1.0, churn_join_rate=1.0,
                      barrier=make_barrier("pssp", staleness=2,
                                           sample_size=2))
            for s in (0, 1)]
    monkeypatch.setenv("PSP_TICK_IMPL", "ref")
    ref = run_sweep(cfgs, backend="jax")
    monkeypatch.setenv("PSP_TICK_IMPL", "interpret")
    ker = run_sweep(cfgs, backend="jax")
    for a, b in zip(ref, ker):
        np.testing.assert_array_equal(a.steps, b.steps)
        # error traces may differ by GEMM-microkernel ulps: XLA picks a
        # different dot microkernel per scenario-batch width (the ref
        # batches rows, the kernel grid iterates them), which reorders
        # the f32 reduction.  Control-plane integers stay exact.
        np.testing.assert_allclose(a.errors, b.errors, rtol=0, atol=1e-6)
        assert a.total_updates == b.total_updates
        assert a.control_messages == b.control_messages


def test_psp_tick_kernel_refuses_fleet_it_cannot_hold():
    """A fleet over the kernel's VMEM budget (the 100k-node sweep) is
    refused at trace time with an error naming P — never deep in Mosaic,
    and never by a silent switch to the reference."""
    import functools
    import jax
    from repro.kernels.psp_tick import psp_tick_tpu
    state, rand, params, leave_n, join_n, masked = _tick_problem(
        0, 2, 100_000, False, False, 1)
    with pytest.raises(ValueError, match="P=100000"):
        jax.eval_shape(functools.partial(
            psp_tick_tpu, k_max=1, has_churn=False, masked=masked),
            state, rand, params, np.float32(0.1), leave_n, join_n)
