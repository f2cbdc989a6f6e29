"""Pallas TPU kernel for one PSP sweep-grid tick's control plane.

One grid tick of the vectorized sweep engine
(:mod:`repro.core.vector_sim_jax`) is two very different workloads: a
*control-plane* update over the ``(B, P)`` scenario state — churn,
finish bookkeeping, the masked-min full-view barrier, the β-sample
barrier predicate, and start/re-poll anchoring — and a *data-plane* SGD
push (minibatch residual + gradient + server update + model-view pull
of the linear task).  The control plane is a swarm of tiny masked
element-wise ops and row reductions, which this module fuses into one
Pallas kernel, one grid step per :data:`KERNEL_ROWS`-row scenario block.
The data plane is two contractions per row block over the ``(P, m, d)``
minibatch blob and the ``(B, P, d)`` node views (32 MB and 64 MB at
paper scale, more than VMEM holds); it runs as XLA code
(:func:`_data_plane`) on the kernel's finisher/starter masks, shared
verbatim by both implementations.

Two implementations, held tick-for-tick identical by
``tests/test_kernels.py``:

* :func:`psp_tick_ref` — pure jnp reference.  β-sampling routes through
  the shared primitives (:func:`repro.core.sampling.sample_peer_indices_jax`
  / ``sample_alive_peer_indices_jax``) and the unified barrier model
  (:mod:`repro.core.barrier_kernel`), i.e. the exact code the SPMD trainer
  uses.  This is what ``impl="auto"`` runs on CPU.
* :func:`psp_tick_tpu` — the Pallas kernel.  Selecting β peers by top-k
  needs a gather, which the TPU vector unit hates; the kernel instead
  consumes the *same* uniform score matrix and evaluates the predicate by
  rank: a lagging peer is inside the β-sample iff fewer than β eligible
  peers precede it in ``(score, index)`` order.  Ties break exactly like
  ``lax.top_k`` (lower index first), so the two paths agree draw-for-draw,
  not just in distribution.

All randomness — step-duration jitter, β-sample scores, churn uniforms
*and* the minibatch blob — is drawn *outside* (plain ``jax.random``
on-device) and passed in, so ref and kernel consume identical noise and
the sweep's RNG stream is independent of ``impl``.

Rows carry a **horizon**: merged sweeps batch scenarios with different
durations, and a row whose horizon lies before this tick's time is
frozen — no churn, no finishes, no decisions, no data-plane update.  The
same gate makes the dead padding ticks of the chunked scan
(:mod:`repro.core.sweep_plan`) semantics-free.

Shapes and state layout (``B`` scenario rows × ``P`` node slots,
``d``-dim model, ``m`` minibatch rows):

========== ============== ================================================
key         shape          meaning
========== ============== ================================================
steps       i32[B, P]      logical clock per node
alive       bool[B, P]     membership (churn / ragged padding)
computing   bool[B, P]     node busy with a local step
event_time  f32[B, P]      finish time while computing, else next check
ready       f32[B, P]      continuous anchor of the current decide attempt
blocked     bool[B, P]     failed its last barrier check
pend_*      i32[B]         carried-over churn events (≤ 1 fires per tick)
w           f32[B, d]      server model (data plane)
pulled      f32[B, P, d]   per-node model view at its last pull
========== ============== ================================================

The data-plane noise is shared across rows (``X`` f32[P, m, d] minibatch
features, ``mb`` f32[P, m] label noise) — each row's marginal is an exact
fresh draw; cross-row correlation is irrelevant for per-row statistics.

VMEM budget: the dominant buffer is the ``P × P`` f32 score tile (~4 MB
at P = 1000; one per row when churn masks the sample); the rank test
streams it in :data:`PEER_CHUNK`-peer slices.  :func:`_vmem_estimate`
bounds the kernel's need, and a fleet over :data:`VMEM_BUDGET` (the
100k-node sweep) is refused at trace time.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import barrier_kernel

__all__ = ["psp_tick_ref", "psp_tick_sharded", "psp_tick_tpu", "STATE_KEYS",
           "POLICY_STATE_KEYS"]


#: data-plane row-block width: the SGD push always runs as GEMMs of
#: exactly this many scenario rows (batches pad up with inert rows).
#: XLA's CPU backend picks its dot strategy — and therefore its f32
#: reduction order — by operand *shape*, so a width that followed the
#: batch (or the per-device shard) would make results depend on how rows
#: are grouped; a constant width makes each row's bits a function of
#: that row alone, which is what keeps sharded sweeps bit-identical to
#: single-device ones.  16 rows amortises the GEMM without inflating
#: small batches too much (measured best of {8, 16, 32} on the Fig-2
#: smoke sweep).
DATA_PLANE_BLOCK = 16


def _data_plane_block(X: jax.Array, diff: jax.Array, fin: jax.Array,
                      start: jax.Array, w: jax.Array, pulled: jax.Array,
                      lr: jax.Array, noise_std: jax.Array, mb: jax.Array,
                      ) -> Tuple[jax.Array, jax.Array]:
    """One fixed-width block of scenario rows' SGD push + model-view pull.

    The whole data plane of :data:`DATA_PLANE_BLOCK` rows in two
    contractions whose shapes depend only on ``(P, m, d)`` — never on the
    batch or shard width (see :data:`DATA_PLANE_BLOCK`).  A GEMM performs
    no cross-row arithmetic, so padded/foreign rows inside a block cannot
    perturb a real row's bits.

    Args:
      X: f32[P, m, d] minibatch features (shared across rows).
      diff: f32[W, P, d] node views minus ground truth.
      fin / start: bool[W, P] finisher and starter masks.
      w: f32[W, d] server models; ``pulled`` f32[W, P, d] node views.
      lr / noise_std: f32[W]; ``mb`` f32[P, m] label noise.

    Returns:
      (w', pulled'): updated server models and node views.
    """
    m = X.shape[1]
    # residual as broadcast-multiply + minor-axis reduce, NOT a batched
    # dot: the per-node (m, d) × (d, W) GEMMs are so small that XLA's
    # batched-dot loop is all dispatch overhead (~1.3× the whole sweep),
    # while the fused multiply-reduce is one flat kernel — and its f32
    # reduction order is width-invariant, which a dot's would not be
    resid = (jnp.sum(X[None] * diff[:, :, None, :], axis=-1)
             - noise_std[:, None, None] * mb[None])
    resid = jnp.where(fin[:, :, None], resid, 0.0)
    gsum = jnp.einsum("kpm,pmd->kd", resid, X,
                      precision=lax.Precision.HIGHEST) / m
    w_new = w - lr[:, None] * gsum
    pulled_new = jnp.where(start[..., None], w_new[:, None, :], pulled)
    return w_new, pulled_new

def _data_plane(state: Dict[str, jax.Array], rand: Dict[str, jax.Array],
                params: Dict[str, jax.Array], fin: jax.Array,
                start: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The whole batch's data plane, in fixed-width row blocks.

    The fin mask zeroes non-finisher residuals, so frozen/inactive rows
    see w − lr·0 — exactly w.  Executed in :data:`DATA_PLANE_BLOCK`-row
    blocks: the GEMM shapes never follow the batch/shard width, so each
    row's bits are independent of how rows are grouped — the
    sharded-sweep bit-identity invariant.  Both tick implementations run
    this same XLA code after their control plane, so they agree bit for
    bit whenever their ``fin``/``start`` masks do.

    It runs under the device scope ``sweep.data_plane``, which a profiler
    trace reads back.

    Returns:
      (w', pulled'): updated server models f32[B, d] and node views
      f32[B, P, d].
    """
    with jax.named_scope("sweep.data_plane"):
        w, pulled = state["w"], state["pulled"]
        B = w.shape[0]
        diff = pulled - params["w_true"][:, None, :]
        W = DATA_PLANE_BLOCK
        Bp = -(-B // W) * W

        def pad(a):
            return a if Bp == B else jnp.concatenate(
                [a, jnp.zeros((Bp - B,) + a.shape[1:], a.dtype)], axis=0)

        d_p, f_p, s_p = pad(diff), pad(fin), pad(start)
        w_p, pu_p = pad(w), pad(pulled)
        lr_p, ns_p = pad(params["lr"]), pad(params["noise_std"])
        blocks = [_data_plane_block(rand["X"], d_p[i:i + W], f_p[i:i + W],
                                    s_p[i:i + W], w_p[i:i + W],
                                    pu_p[i:i + W], lr_p[i:i + W],
                                    ns_p[i:i + W], rand["mb"])
                  for i in range(0, Bp, W)]
        return (jnp.concatenate([b[0] for b in blocks])[:B],
                jnp.concatenate([b[1] for b in blocks])[:B])


#: carried tick state, in canonical order (control plane, then data plane)
STATE_KEYS = ("steps", "alive", "computing", "event_time", "ready",
              "blocked", "pend_leave", "pend_join", "w", "pulled")

#: adaptive barrier-policy state, carried *only* when the batch contains
#: adaptive rows (``adaptive=True``) — static batches pass zero-width
#: policy state (the keys are simply absent) and compile the exact
#: pre-policy tick, so golden traces and kernel paths are unchanged.
#: ``pol_thr`` i32[B] is DSSP's dynamic staleness threshold, ``pol_ema``
#: f32[B, P] Elastic-BSP's per-worker duration EMA, ``pol_beta`` i32[B]
#: the β-annealing rows' current sample size.
POLICY_STATE_KEYS = ("pol_thr", "pol_ema", "pol_beta")

_I32_MAX = np.iinfo(np.int32).max
_I32_MIN = np.iinfo(np.int32).min


# --------------------------------------------------------------------------- #
# pure-jnp reference (the CPU path of ops.psp_tick)
# --------------------------------------------------------------------------- #
def psp_tick_ref(state: Dict[str, jax.Array], rand: Dict[str, jax.Array],
                 params: Dict[str, jax.Array], t: jax.Array,
                 leave_n: jax.Array, join_n: jax.Array, *,
                 k_max: int, has_churn: bool, masked: bool,
                 adaptive: bool = False,
                 ) -> Tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
    """One full tick, batched over B scenario rows (pure jnp).

    Args:
      state: the tick-state pytree (:data:`STATE_KEYS`; plus
        :data:`POLICY_STATE_KEYS` when ``adaptive``).
      rand: pre-drawn noise — ``dur`` f32[B, P] step-duration jitter;
        ``X`` f32[P, m, d] / ``mb`` f32[P, m] shared minibatch blob; plus
        ``scores`` (f32[B, P, P] when ``masked`` else f32[P, P]) or
        ``u1`` f32[P] (β = 1 fast path) when ``k_max > 0``; plus
        ``leave``/``join`` f32[B, P] when ``has_churn``.
      params: per-row policy arrays — ``staleness``/``beta_clip``/
        ``dist_hops`` i32[B]; ``is_asp``/``full_view``/``sampled`` bool[B];
        ``compute_time`` f32[B, P]; ``valid_slot`` bool[B, P] (ragged
        padding mask); ``horizon``/``lr``/``noise_std`` f32[B];
        ``w_true`` f32[B, d]; scalars ``eps``/``poll``.  When
        ``adaptive``: ``is_dssp``/``is_ebsp``/``is_anneal`` bool[B] row
        tags plus ``pol_lo``/``beta_lo`` i32[B] lower bounds and
        ``ebsp_range``/``ebsp_alpha`` f32[B] Elastic-BSP knobs (upper
        bounds reuse ``staleness``/``beta_clip``).
      t: f32[] — this tick's grid time; rows with ``horizon < t`` freeze.
      leave_n / join_n: i32[B] — churn events due this tick.
      k_max: static max sample-slot count over the batch.
      has_churn: static — whether churn state/noise is present.
      masked: static — per-row alive-masked sampling (churn or ragged).
      adaptive: static — whether the batch carries adaptive-policy rows
        (and therefore the :data:`POLICY_STATE_KEYS` state/param arrays).

    Returns:
      (new_state, out) where ``out`` holds ``fin``/``start`` bool[B, P]
      node masks and ``n_fin``/``ctrl`` i32[B] row counters.
    """
    steps, alive = state["steps"], state["alive"]
    computing, blocked = state["computing"], state["blocked"]
    event_time, ready = state["event_time"], state["ready"]
    B, P = steps.shape
    eps, poll = params["eps"], params["poll"]
    iota = jnp.arange(P, dtype=jnp.int32)
    #: row liveness: frozen past the row horizon (merged durations and the
    #: chunk scheduler's dead padding ticks both route through this gate)
    active = t <= params["horizon"] + eps

    # 0. churn: at most one pre-sampled leave/join fires per row per tick
    #    (surplus carries forward in pend_*; Poisson totals are preserved)
    if has_churn:
        pend_l = state["pend_leave"] + leave_n
        pend_j = state["pend_join"] + join_n
        do_l = active & (pend_l > 0) & (jnp.sum(alive, axis=1) > 2)
        victim = barrier_kernel.churn_victim(rand["leave"], alive)
        v_oh = victim[:, None] == iota
        alive = alive & ~(do_l[:, None] & v_oh)
        pool = ~alive & params["valid_slot"]
        do_j = active & (pend_j > 0) & jnp.any(pool, axis=1)
        joiner = barrier_kernel.churn_joiner(rand["join"], alive,
                                             params["valid_slot"])
        sel = do_j[:, None] & (joiner[:, None] == iota)
        alive = alive | sel
        fresh = jnp.max(jnp.where(alive, steps, _I32_MIN), axis=1)
        steps = jnp.where(sel, fresh[:, None], steps)
        computing = computing & ~sel
        event_time = jnp.where(sel, t, event_time)
        ready = jnp.where(sel, t, ready)
        blocked = blocked & ~sel
        pend_leave = jnp.where(active, pend_l - (pend_l > 0),
                               state["pend_leave"])
        pend_join = jnp.where(active, pend_j - (pend_j > 0),
                              state["pend_join"])
    else:
        pend_leave, pend_join = state["pend_leave"], state["pend_join"]

    # 1. finishes: advance steps, become "deciding"; the masked data-plane
    #    push at the bottom consumes this fin mask
    fin = computing & alive & (event_time <= t + eps) & active[:, None]
    any_fin = jnp.any(fin, axis=1)
    row_last = jnp.max(jnp.where(fin, event_time, -jnp.inf), axis=1)
    row_unblock = jnp.where(any_fin, jnp.minimum(row_last, t), t)
    steps = steps + fin
    computing = computing & ~fin
    ready = jnp.where(fin, event_time, ready)
    blocked = blocked & ~fin

    # 2. barrier decisions for every due deciding node, through the
    #    unified barrier model (single source with the SPMD trainer)
    cand = ~computing & alive & (event_time <= t + eps) & active[:, None]
    stal = jnp.broadcast_to(params["staleness"][:, None], (B, P))
    beta_eff = params["beta_clip"][:, None]
    if adaptive:
        # adaptive rows swap their *effective* staleness/β in before the
        # (unchanged) predicates run: DSSP rows read the carried dynamic
        # threshold, Elastic-BSP rows their per-worker EMA step credit,
        # β-annealing rows the carried sample size — static rows keep the
        # per-row constants bit-for-bit
        slack = barrier_kernel.elastic_slack(
            state["pol_ema"], params["ebsp_range"][:, None], alive)
        stal = jnp.where(params["is_dssp"][:, None],
                         state["pol_thr"][:, None],
                         jnp.where(params["is_ebsp"][:, None], slack, stal))
        beta_eff = jnp.where(params["is_anneal"], state["pol_beta"],
                             params["beta_clip"])[:, None]
    pass_fv = barrier_kernel.full_view_allowed(steps, stal, alive)
    if k_max > 0:
        pass_sm, n_sampled = barrier_kernel.sampled_allowed(
            steps, stal, k_max, beta=beta_eff,
            scores=rand.get("scores"), u=rand.get("u1"),
            alive=alive if masked else None)
    else:
        pass_sm = jnp.ones((B, P), dtype=bool)
        n_sampled = jnp.zeros((B, P), dtype=jnp.int32)
    passed = jnp.where(params["is_asp"][:, None], True,
                       jnp.where(params["full_view"][:, None],
                                 pass_fv, pass_sm))
    ctrl = jnp.sum(
        jnp.where(cand, n_sampled * params["dist_hops"][:, None], 0),
        axis=1).astype(jnp.int32)

    # 3. starts / re-polls, anchored at continuous ready times
    start = cand & passed
    t0 = jnp.where(blocked & params["full_view"][:, None],
                   jnp.maximum(row_unblock[:, None], ready), ready)
    dur = barrier_kernel.step_duration(rand["dur"], params["compute_time"])
    event_time = jnp.where(start, t0 + dur, event_time)
    computing = computing | start
    fail = cand & ~passed
    blocked = (blocked | fail) & ~start
    sm_fail = fail & params["sampled"][:, None]
    ready = jnp.where(sm_fail, ready + poll, ready)
    event_time = jnp.where(sm_fail, ready, event_time)

    # 3b. adaptive-policy state updates: decisions above used the OLD
    #     state; the new state is a pure function of this tick's
    #     observations (post-finish step spread, starters' drawn
    #     durations) — frozen rows (past horizon) keep their state
    if adaptive:
        gap = barrier_kernel.progress_gap(steps, alive)
        pol_thr = jnp.where(
            params["is_dssp"] & active,
            jnp.clip(gap, params["pol_lo"], params["staleness"]),
            state["pol_thr"]).astype(jnp.int32)
        pol_beta = jnp.where(
            params["is_anneal"] & active,
            jnp.clip(params["beta_lo"] + gap - params["staleness"],
                     params["beta_lo"], params["beta_clip"]),
            state["pol_beta"]).astype(jnp.int32)
        al = params["ebsp_alpha"][:, None]
        pol_ema = jnp.where(
            params["is_ebsp"][:, None] & start,
            (1.0 - al) * state["pol_ema"] + al * dur,
            state["pol_ema"])

    # 4. data plane: masked SGD push of every finisher, then the starters
    #    pull the updated server model into their view (:func:`_data_plane`)
    w, pulled = _data_plane(state, rand, params, fin, start)

    new_state = {"steps": steps, "alive": alive, "computing": computing,
                 "event_time": event_time, "ready": ready,
                 "blocked": blocked, "pend_leave": pend_leave,
                 "pend_join": pend_join, "w": w, "pulled": pulled}
    if adaptive:
        new_state.update(pol_thr=pol_thr, pol_ema=pol_ema,
                         pol_beta=pol_beta)
    out = {"fin": fin, "start": start,
           "n_fin": jnp.sum(fin, axis=1).astype(jnp.int32), "ctrl": ctrl}
    return new_state, out

# --------------------------------------------------------------------------- #
# node-sharded reference (collectives over the sweep mesh's "nodes" axis)
# --------------------------------------------------------------------------- #
def _arg_first_max(s: jax.Array, gids: jax.Array, sentinel: int,
                   axis_name: str) -> jax.Array:
    """Global index of each row's first maximum, across node shards.

    ``s`` (B, P_loc) is sentinel-masked scores (dead slots −1.0), ``gids``
    the shard's global node ids.  Exactly ``jnp.argmax`` over the full
    row: the maximum is an exact f32 ``pmax`` and the tie-break takes the
    lowest global index (first occurrence — global node order is shard
    order × local order), so the collective form is bit-free of the
    factorization.
    """
    m = lax.pmax(jnp.max(s, axis=1), axis_name)
    i_loc = jnp.min(jnp.where(s == m[:, None], gids[None, :], sentinel),
                    axis=1)
    return lax.pmin(i_loc, axis_name)


def psp_tick_sharded(state: Dict[str, jax.Array], rand: Dict[str, jax.Array],
                     params: Dict[str, jax.Array], t: jax.Array,
                     leave_n: jax.Array, join_n: jax.Array, *,
                     k_max: int, has_churn: bool, masked: bool,
                     adaptive: bool = False, node_axis: str = "nodes",
                     ) -> Tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
    """One full tick on node-sharded state: :func:`psp_tick_ref` with the
    cross-node reductions as collectives over ``node_axis``.

    Called under ``shard_map`` on a ``(rows, nodes)`` mesh
    (:mod:`repro.core.vector_sim_jax`): every node-dimensioned operand —
    state (``steps`` … ``pulled``), per-node noise (``dur``, score rows,
    churn uniforms, the minibatch blob) and per-node params
    (``compute_time``, ``valid_slot``) — arrives sliced to the shard's
    contiguous ``P_loc = P / nodes`` node block, and β-sample score rows
    are keyed by global node id so each shard draws exactly its slice.

    **Bit-identity contract** (the reason this function exists instead of
    a generic re-layout): every output element equals
    :func:`psp_tick_ref`'s for *any* nodes-axis size, because each
    cross-node reduction is one of

    * an order-free exact collective — ``pmin``/``pmax`` over step
      counters and event times, integer ``psum`` counts, the
      first-argmax churn victim/joiner selection (:func:`_arg_first_max`);
    * a pure selection over a gathered full-width operand — the β-sample
      ``top_k``/indexing consumes all-gathered ``steps``/``alive`` (bools
      and i32 gather bit-exactly) with the shard's own score rows;
    * the data-plane contraction on gathered full-width inputs — the one
      genuine f32 reduction over P keeps the reference's exact operand
      shapes (:func:`_data_plane_block` at width P), so XLA picks the
      same reduction order for every factorization.  The *stored* blob
      and views stay node-sliced; only one tick's worth is ever
      materialized full-width.

    The nodes axis must divide P exactly (the planner guarantees it) —
    a padded node slot would widen these reductions and void the
    contract.
    """
    steps, alive = state["steps"], state["alive"]
    computing, blocked = state["computing"], state["blocked"]
    event_time, ready = state["event_time"], state["ready"]
    B, Pl = steps.shape
    i32 = jnp.int32
    eps, poll = params["eps"], params["poll"]
    gids = lax.axis_index(node_axis) * Pl + jnp.arange(Pl, dtype=i32)
    active = t <= params["horizon"] + eps

    def nsum(x):
        return lax.psum(jnp.sum(x, axis=1), node_axis)

    def gather(x, axis=1):
        return lax.all_gather(x, node_axis, axis=axis, tiled=True)

    # 0. churn — mirrors psp_tick_ref phase 0 with the row reductions
    #    (alive count, victim/joiner argmax, freshest step) collective
    if has_churn:
        pend_l = state["pend_leave"] + leave_n
        pend_j = state["pend_join"] + join_n
        do_l = active & (pend_l > 0) & (nsum(alive) > 2)
        victim = _arg_first_max(jnp.where(alive, rand["leave"], -1.0),
                                gids, _I32_MAX, node_axis)
        alive = alive & ~(do_l[:, None] & (victim[:, None] == gids[None]))
        pool = ~alive & params["valid_slot"]
        do_j = active & (pend_j > 0) & (nsum(pool) > 0)
        joiner = _arg_first_max(jnp.where(pool, rand["join"], -1.0),
                                gids, _I32_MAX, node_axis)
        sel = do_j[:, None] & (joiner[:, None] == gids[None])
        alive = alive | sel
        fresh = lax.pmax(jnp.max(jnp.where(alive, steps, _I32_MIN), axis=1),
                         node_axis)
        steps = jnp.where(sel, fresh[:, None], steps)
        computing = computing & ~sel
        event_time = jnp.where(sel, t, event_time)
        ready = jnp.where(sel, t, ready)
        blocked = blocked & ~sel
        pend_leave = jnp.where(active, pend_l - (pend_l > 0),
                               state["pend_leave"])
        pend_join = jnp.where(active, pend_j - (pend_j > 0),
                              state["pend_join"])
    else:
        pend_leave, pend_join = state["pend_leave"], state["pend_join"]

    # 1. finishes (elementwise; row_last is an exact f32 max)
    fin = computing & alive & (event_time <= t + eps) & active[:, None]
    any_fin = nsum(fin) > 0
    row_last = lax.pmax(jnp.max(jnp.where(fin, event_time, -jnp.inf),
                                axis=1), node_axis)
    row_unblock = jnp.where(any_fin, jnp.minimum(row_last, t), t)
    steps = steps + fin
    computing = computing & ~fin
    ready = jnp.where(fin, event_time, ready)
    blocked = blocked & ~fin

    # 2. barrier decisions.  The full-view min is a pmin; the β-sample
    #    consults gathered steps/alive (exact) with the shard's own
    #    node-keyed score rows — selection only, no cross-shard f32 math
    cand = ~computing & alive & (event_time <= t + eps) & active[:, None]
    steps_full = gather(steps)
    P = steps_full.shape[1]
    piota = jnp.arange(P, dtype=i32)
    stal = jnp.broadcast_to(params["staleness"][:, None], (B, Pl))
    beta_eff = params["beta_clip"][:, None]
    if adaptive:
        live = jnp.where(alive, state["pol_ema"], 0.0)
        mx = lax.pmax(jnp.max(live, axis=1), node_axis)
        frac = 1.0 - state["pol_ema"] / jnp.maximum(mx[:, None], 1e-9)
        slack = jnp.floor(params["ebsp_range"][:, None] * frac).astype(i32)
        stal = jnp.where(params["is_dssp"][:, None],
                         state["pol_thr"][:, None],
                         jnp.where(params["is_ebsp"][:, None], slack, stal))
        beta_eff = jnp.where(params["is_anneal"], state["pol_beta"],
                             params["beta_clip"])[:, None]
    min_alive = lax.pmin(jnp.min(jnp.where(alive, steps, _I32_MAX), axis=1),
                         node_axis)
    pass_fv = steps - min_alive[:, None] <= stal
    if k_max > 0:
        if masked:
            # sample_alive_peer_indices_jax with the deciding axis
            # sliced: per-node top-k over the full gathered peer width
            alive_full = gather(alive)
            sc = jnp.where(~alive_full[:, None, :]
                           | (gids[None, :, None] == piota[None, None, :]),
                           2.0, rand["scores"])            # (B, Pl, P)
            neg, take = lax.top_k(-sc, k_max)
            valid = -neg < 1.5
            peer = jnp.take_along_axis(
                jnp.broadcast_to(steps_full[:, None, :], (B, Pl, P)),
                take, axis=-1)
        elif k_max == 1:
            # sample_peer_indices_jax's β = 1 branch on global node ids
            draw = jnp.floor(rand["u1"] * max(P - 1, 1)).astype(i32)
            take = jnp.minimum(draw + (draw >= gids), P - 1)   # (Pl,)
            peer = steps_full[:, take][:, :, None]
            valid = jnp.broadcast_to(
                jnp.arange(1) < P - 1, peer.shape)
        else:
            # shared-score top-k: the shard draws its deciding nodes'
            # score rows (global-node keyed), peers span the full width
            sc = jnp.where(gids[:, None] == piota[None, :], 2.0,
                           rand["scores"])                 # (Pl, P)
            _, take = lax.top_k(-sc, k_max)
            peer = steps_full[:, take]                     # (B, Pl, k)
            valid = jnp.broadcast_to(
                jnp.arange(k_max) < P - 1, peer.shape)
        valid = valid & (jnp.arange(peer.shape[-1]) < beta_eff[..., None])
        lag_ok = steps[..., None] - peer <= stal[..., None]
        pass_sm = jnp.all(lag_ok | ~valid, axis=-1)
        n_sampled = jnp.sum(valid, axis=-1)
    else:
        pass_sm = jnp.ones((B, Pl), dtype=bool)
        n_sampled = jnp.zeros((B, Pl), dtype=i32)
    passed = jnp.where(params["is_asp"][:, None], True,
                       jnp.where(params["full_view"][:, None],
                                 pass_fv, pass_sm))
    ctrl = lax.psum(jnp.sum(
        jnp.where(cand, n_sampled * params["dist_hops"][:, None], 0),
        axis=1), node_axis).astype(i32)

    # 3. starts / re-polls (elementwise given the per-row row_unblock)
    start = cand & passed
    t0 = jnp.where(blocked & params["full_view"][:, None],
                   jnp.maximum(row_unblock[:, None], ready), ready)
    dur = barrier_kernel.step_duration(rand["dur"], params["compute_time"])
    event_time = jnp.where(start, t0 + dur, event_time)
    computing = computing | start
    fail = cand & ~passed
    blocked = (blocked | fail) & ~start
    sm_fail = fail & params["sampled"][:, None]
    ready = jnp.where(sm_fail, ready + poll, ready)
    event_time = jnp.where(sm_fail, ready, event_time)

    # 3b. adaptive-policy updates: progress_gap from exact collectives
    if adaptive:
        mxs = lax.pmax(jnp.max(jnp.where(alive, steps, _I32_MIN), axis=1),
                       node_axis)
        mns = lax.pmin(jnp.min(jnp.where(alive, steps, _I32_MAX), axis=1),
                       node_axis)
        gap = jnp.where(nsum(alive) > 0, mxs - mns, 0)
        pol_thr = jnp.where(
            params["is_dssp"] & active,
            jnp.clip(gap, params["pol_lo"], params["staleness"]),
            state["pol_thr"]).astype(i32)
        pol_beta = jnp.where(
            params["is_anneal"] & active,
            jnp.clip(params["beta_lo"] + gap - params["staleness"],
                     params["beta_lo"], params["beta_clip"]),
            state["pol_beta"]).astype(i32)
        al = params["ebsp_alpha"][:, None]
        pol_ema = jnp.where(
            params["is_ebsp"][:, None] & start,
            (1.0 - al) * state["pol_ema"] + al * dur,
            state["pol_ema"])

    # 4. data plane: the one f32 reduction over P.  The contraction runs
    #    on gathered full-width operands at the reference's exact shapes
    #    (any node-sliced partial-sum scheme would change the reduction
    #    order and break cross-factorization bit-identity); the server
    #    model is per-row (replicated over the nodes axis), so every
    #    shard computes the identical w and pulls only its own view slice.
    #    Device scope ``sweep.data_plane``, as in :func:`_data_plane`.
    with jax.named_scope("sweep.data_plane"):
        X = gather(rand["X"], axis=0)               # (P, m, d)
        mbn = gather(rand["mb"], axis=0)            # (P, m)
        fin_full = gather(fin)
        pulled_full = gather(state["pulled"])       # (B, P, d)
        w = state["w"]
        diff = pulled_full - params["w_true"][:, None, :]
        W = DATA_PLANE_BLOCK
        Bp = -(-B // W) * W

        def pad(a):
            return a if Bp == B else jnp.concatenate(
                [a, jnp.zeros((Bp - B,) + a.shape[1:], a.dtype)], axis=0)

        d_p, f_p = pad(diff), pad(fin_full)
        w_p = pad(w)
        lr_p, ns_p = pad(params["lr"]), pad(params["noise_std"])
        zero_pull = jnp.zeros((W,) + pulled_full.shape[1:], pulled_full.dtype)
        w_blocks = [_data_plane_block(X, d_p[i:i + W], f_p[i:i + W],
                                      jnp.zeros((W, X.shape[0]), bool),
                                      w_p[i:i + W], zero_pull,
                                      lr_p[i:i + W], ns_p[i:i + W], mbn)[0]
                    for i in range(0, Bp, W)]
        w = jnp.concatenate(w_blocks)[:B]
        pulled = jnp.where(start[..., None], w[:, None, :], state["pulled"])

    new_state = {"steps": steps, "alive": alive, "computing": computing,
                 "event_time": event_time, "ready": ready,
                 "blocked": blocked, "pend_leave": pend_leave,
                 "pend_join": pend_join, "w": w, "pulled": pulled}
    if adaptive:
        new_state.update(pol_thr=pol_thr, pol_ema=pol_ema,
                         pol_beta=pol_beta)
    out = {"fin": fin, "start": start,
           "n_fin": nsum(fin).astype(i32), "ctrl": ctrl}
    return new_state, out


# --------------------------------------------------------------------------- #
# Pallas kernel: the control plane, one grid step per row block
# --------------------------------------------------------------------------- #
#: scenario rows per kernel grid step: the TPU's sublane tile, so every
#: ``(rows, P)`` block satisfies Mosaic's (8, 128) block rule
KERNEL_ROWS = 8

#: peers per step of the β-sample rank test: the test streams the peer
#: axis through ``(chunk, P)`` tiles, so its transients stay a few MB at
#: paper scale (P = 1000) instead of ``(rows, P, P)``
PEER_CHUNK = 128

#: scoped-VMEM ceiling for one kernel instance (v5e has 128 MiB per
#: core); a shape whose estimate exceeds it is refused at trace time
VMEM_BUDGET = 96 << 20


def _peer_chunking(P: int) -> Tuple[int, int]:
    """(chunk, padded peer count) for the rank test's peer stream."""
    chunk = min(PEER_CHUNK, -(-P // 8) * 8)
    return chunk, -(-P // chunk) * chunk


def _vmem_estimate(P: int, k_max: int, masked: bool) -> int:
    """Bytes of VMEM the kernel needs at node count ``P`` (an upper
    estimate: double-buffered blocks plus the rank test's live tiles)."""
    lanes = -(-P // 128) * 128
    block = KERNEL_ROWS * lanes * 4                 # one (rows, P) tile
    total = 2 * 20 * block + 40 * block             # I/O blocks + values
    chunk, Pp = _peer_chunking(P)
    if k_max > 1 or masked:
        total += 2 * (KERNEL_ROWS if masked else 1) * Pp * lanes * 4
    if k_max > 0:
        total += 12 * chunk * lanes * 4
    return total


def _first_argmax_rows(scores: jax.Array, mask: jax.Array,
                       iota: jax.Array, P: int) -> jax.Array:
    """Per-row index of the first maximum of ``scores`` under ``mask``.

    The lowest index attaining each row's masked maximum — exactly
    ``jnp.argmax(where(mask, scores, -1), axis=1)`` for scores in [0, 1),
    written with reductions only (no argmax lowering dependence).
    Shapes: ``scores``/``mask`` (W, P), ``iota`` (1, P) → (W, 1).
    """
    s = jnp.where(mask, scores, -1.0)
    mx = jnp.max(s, axis=1, keepdims=True)
    return jnp.min(jnp.where(s == mx, iota, P), axis=1, keepdims=True)


def _any(x: jax.Array, axis: int) -> jax.Array:
    """``jnp.any`` as an i32 max (Mosaic reduces i32, not i1, vectors)."""
    return jnp.max(x.astype(jnp.int32), axis=axis, keepdims=True) > 0


def _peer_col(row: jax.Array, j0, n: int) -> jax.Array:
    """Entries ``j0 … j0+n−1`` of a ``(1, P)`` i32 lane vector as an
    ``(n, 1)`` column (0 past P): select + lane sum, exact for integers
    and free of transposes, gathers and matmuls."""
    P = row.shape[1]
    jj = lax.broadcasted_iota(jnp.int32, (n, P), 0) + j0
    pp = lax.broadcasted_iota(jnp.int32, (n, P), 1)
    return jnp.sum(jnp.where(jj == pp, row, 0), axis=1, keepdims=True)


def _rank_test_row(s_row, st_row, a_row, scores, P: int, Pp: int,
                   chunk: int):
    """β-sample rank test of one scenario row, streamed over peer chunks.

    ``s_row``/``st_row`` (1, P) i32: steps and effective staleness of the
    deciding nodes (lanes); ``a_row`` (1, P) i32 alive mask (masked
    rows) or None; ``scores(j0)`` → the ``(chunk, P)`` tile of
    transposed scores ``[peer, decider]``.  Pass 1 finds each decider's
    lowest-(score, index) bad peer — a lexicographic min merged across
    chunks; pass 2 counts the eligible peers ordered before it.  Returns
    ``(any_bad, cnt, n_elig)`` (1, P) — the rank form of the top-k
    sample: the bad peer is sampled iff ``cnt < β``.
    """
    i32 = jnp.int32
    pp = lax.broadcasted_iota(i32, (chunk, P), 1)
    # a peer lags too far iff its step < the decider's step − staleness
    thr = s_row - st_row

    def tile(c):
        j0 = pl.multiple_of(c * chunk, chunk)
        jj = lax.broadcasted_iota(i32, (chunk, P), 0) + j0
        elig = (jj != pp) & (jj < P)
        if a_row is not None:
            elig = elig & (_peer_col(a_row, j0, chunk) != 0)
        bad = elig & (_peer_col(s_row, j0, chunk) < thr)
        return jj, elig, bad, scores(j0)

    def pass1(c, carry):
        mbs, mbi, nbad = carry
        jj, _, bad, sc = tile(c)
        cm = jnp.min(jnp.where(bad, sc, 3.0), axis=0, keepdims=True)
        ci = jnp.min(jnp.where(bad & (sc == cm), jj, P), axis=0,
                     keepdims=True)
        mbi = jnp.where(cm < mbs, ci,
                        jnp.where(cm == mbs, jnp.minimum(mbi, ci), mbi))
        return (jnp.minimum(mbs, cm), mbi,
                nbad + jnp.sum(bad.astype(i32), axis=0, keepdims=True))

    n = Pp // chunk
    mbs, mbi, nbad = lax.fori_loop(
        0, n, pass1, (jnp.full((1, P), 3.0, jnp.float32),
                      jnp.full((1, P), P, i32), jnp.zeros((1, P), i32)))

    def pass2(c, carry):
        cnt, nel = carry
        jj, elig, _, sc = tile(c)
        before = elig & ((sc < mbs) | ((sc == mbs) & (jj < mbi)))
        return (cnt + jnp.sum(before.astype(i32), axis=0, keepdims=True),
                nel + jnp.sum(elig.astype(i32), axis=0, keepdims=True))

    cnt, n_elig = lax.fori_loop(
        0, n, pass2, (jnp.zeros((1, P), i32), jnp.zeros((1, P), i32)))
    return nbad > 0, cnt, n_elig


def _peer_step_row(s_row, take, P: int, Pp: int, chunk: int):
    """``s_row[take]`` for a ``(1, P)`` index vector: the β = 1 peer's
    step, as a select + sublane sum over peer chunks (exact, no gather)."""
    i32 = jnp.int32

    def body(c, acc):
        j0 = pl.multiple_of(c * chunk, chunk)
        jj = lax.broadcasted_iota(i32, (chunk, P), 0) + j0
        return acc + jnp.sum(
            jnp.where(jj == take, _peer_col(s_row, j0, chunk), 0),
            axis=0, keepdims=True)

    return lax.fori_loop(0, Pp // chunk, body, jnp.zeros((1, P), i32))


def _tick_kernel(*refs, k_max: int, has_churn: bool, masked: bool,
                 use_u1: bool, adaptive: bool, W: int, P: int):
    """Kernel body: one W-row block's control-plane tick in VMEM."""
    it = iter(refs)
    steps_ref, alive_ref, computing_ref, event_ref, ready_ref, blocked_ref,\
        pl_ref, pj_ref = (next(it) for _ in range(8))
    ln_ref, jn_ref = next(it), next(it)
    u_dur_ref = next(it)
    samp_ref = next(it) if (k_max > 0) else None
    ul_ref = next(it) if has_churn else None
    uj_ref = next(it) if has_churn else None
    ct_ref, vs_ref = next(it), next(it)
    stal_ref, beta_ref, asp_ref, fv_ref, sm_ref, dh_ref = \
        (next(it) for _ in range(6))
    if adaptive:
        # adaptive-policy operands (zero-width for static batches: absent)
        thr_ref, pbeta_ref, ema_ref = (next(it) for _ in range(3))
        dssp_ref, ebsp_ref, ann_ref, lo_ref, blo_ref = \
            (next(it) for _ in range(5))
        ebr_ref, eba_ref = next(it), next(it)
    hz_ref = next(it)
    t_ref, eps_ref, poll_ref = next(it), next(it), next(it)
    (o_steps, o_alive, o_comp, o_event, o_ready, o_block, o_pl, o_pj,
     o_fin, o_start, o_nfin, o_ctrl) = (next(it) for _ in range(12))
    if adaptive:
        o_thr, o_ema, o_beta = (next(it) for _ in range(3))

    i32 = jnp.int32
    steps = steps_ref[...]                      # (W, P) i32
    alive = alive_ref[...] != 0
    computing = computing_ref[...] != 0
    event_time = event_ref[...]
    ready = ready_ref[...]
    blocked = blocked_ref[...] != 0
    valid_slot = vs_ref[...] != 0
    # (1, 1) operands broadcast; no scalar extraction from vector memory
    t, eps, poll = t_ref[...], eps_ref[...], poll_ref[...]
    stal, beta = stal_ref[...], beta_ref[...]   # (W, 1) i32
    active = t <= hz_ref[...] + eps             # (W, 1) row liveness
    iota = lax.broadcasted_iota(i32, (1, P), 1)

    # 0. churn: one pre-sampled leave/join per row per tick
    if has_churn:
        pend_l = pl_ref[...] + ln_ref[...]      # (W, 1)
        pend_j = pj_ref[...] + jn_ref[...]
        n_alive = jnp.sum(alive.astype(i32), axis=1, keepdims=True)
        do_l = active & (pend_l > 0) & (n_alive > 2)
        vid = _first_argmax_rows(ul_ref[...], alive, iota, P)
        alive = alive & ~(do_l & (iota == vid))
        pool = ~alive & valid_slot
        do_j = active & (pend_j > 0) & _any(pool, 1)
        jid = _first_argmax_rows(uj_ref[...], pool, iota, P)
        sel = do_j & (iota == jid)
        alive = alive | sel
        fresh = jnp.max(jnp.where(alive, steps, _I32_MIN), axis=1,
                        keepdims=True)
        steps = jnp.where(sel, fresh, steps)
        computing = computing & ~sel
        event_time = jnp.where(sel, t, event_time)
        ready = jnp.where(sel, t, ready)
        blocked = blocked & ~sel
        o_pl[...] = jnp.where(active, pend_l - (pend_l > 0), pl_ref[...])
        o_pj[...] = jnp.where(active, pend_j - (pend_j > 0), pj_ref[...])
    else:
        o_pl[...] = pl_ref[...]
        o_pj[...] = pj_ref[...]

    # 1. finishes
    fin = computing & alive & (event_time <= t + eps) & active
    any_fin = _any(fin, 1)
    row_last = jnp.max(jnp.where(fin, event_time, -jnp.inf), axis=1,
                       keepdims=True)
    row_unblock = jnp.where(any_fin, jnp.minimum(row_last, t), t)
    steps = steps + fin.astype(i32)
    computing = computing & ~fin
    ready = jnp.where(fin, event_time, ready)
    blocked = blocked & ~fin

    # 2. barrier decisions
    cand = ~computing & alive & (event_time <= t + eps) & active
    stal_eff, beta_eff = stal, beta
    if adaptive:
        # effective staleness/β per row, via the same shared helper (and
        # the same op order) as psp_tick_ref — ref ↔ kernel stay
        # bit-identical for adaptive rows too; static rows read the
        # constant columns unchanged
        is_dssp = dssp_ref[...] != 0            # (W, 1)
        is_ebsp = ebsp_ref[...] != 0
        is_ann = ann_ref[...] != 0
        slack = barrier_kernel.elastic_slack(ema_ref[...], ebr_ref[...],
                                             alive)
        stal_eff = jnp.where(is_dssp, thr_ref[...],
                             jnp.where(is_ebsp, slack, stal))   # (W, P)
        beta_eff = jnp.where(is_ann, pbeta_ref[...], beta)      # (W, 1)
    stal_eff = jnp.broadcast_to(stal_eff, (W, P))
    min_alive = jnp.min(jnp.where(alive, steps, _I32_MAX), axis=1,
                        keepdims=True)
    pass_fv = steps - min_alive <= stal_eff
    if k_max == 0:
        fail_sm = None
        n_sampled = jnp.zeros((W, P), dtype=i32)
    else:
        # per-row β-sample tests, assembled back into (W, P) by row select
        chunk, Pp = _peer_chunking(P)
        rows = lax.broadcasted_iota(i32, (W, P), 0)
        alive_i = alive.astype(i32)
        if use_u1:
            # β = 1 fast path: one shared uniform over the P−1 non-self
            # slots, the exact formula of sample_peer_indices_jax's
            # k == 1 branch; the peer's step by select (exact)
            draw = jnp.floor(samp_ref[...] * max(P - 1, 1)).astype(i32)
            take = jnp.minimum(draw + (draw >= iota).astype(i32), P - 1)
            step_peer = jnp.zeros((W, P), i32)
            for r in range(W):
                step_peer = jnp.where(
                    rows == r, _peer_step_row(steps[r:r + 1], take, P, Pp,
                                              chunk), step_peer)
            # the one peer is consulted iff min(β, P − 1) ≥ 1
            fail_sm = (steps - step_peer > stal_eff) \
                & (jnp.minimum(beta_eff, P - 1) >= 1)
            n_sampled = jnp.broadcast_to(
                jnp.minimum(beta_eff, P - 1), (W, P)).astype(i32)
        else:
            # rank form of the top-k β-sample: the lowest-(score, index)
            # bad peer is inside the sample iff fewer than β eligible
            # peers precede it — identical to lax.top_k selection.  The
            # shared-draw path (masked=False) matches the unmasked
            # reference primitive: every non-self peer is in the pool —
            # the sweep engine only takes it when the batch is fully alive
            any_bad = jnp.zeros((W, P), i32)
            cnt = jnp.zeros((W, P), i32)
            n_elig = jnp.zeros((W, P), i32)
            for r in range(W):
                if masked:
                    sc = lambda j0, r=r: samp_ref[r, pl.ds(j0, chunk), :]
                else:
                    sc = lambda j0: samp_ref[pl.ds(j0, chunk), :]
                ab, c, ne = _rank_test_row(
                    steps[r:r + 1], stal_eff[r:r + 1],
                    alive_i[r:r + 1] if masked else None, sc, P, Pp, chunk)
                here = rows == r
                any_bad = jnp.where(here, ab.astype(i32), any_bad)
                cnt = jnp.where(here, c, cnt)
                n_elig = jnp.where(here, ne, n_elig)
            fail_sm = (any_bad != 0) & (cnt < beta_eff)
            n_sampled = jnp.minimum(beta_eff, n_elig)
    is_asp, full_view = asp_ref[...] != 0, fv_ref[...] != 0    # (W, 1)
    # where(asp, True, where(fv, pass_fv, pass_sm)) in boolean algebra
    # (Mosaic has no select over i1 vectors)
    passed = is_asp | (full_view & pass_fv)
    passed = passed | ~full_view if fail_sm is None \
        else passed | (~full_view & ~fail_sm)
    o_ctrl[...] = jnp.sum(jnp.where(cand, n_sampled * dh_ref[...], 0),
                          axis=1, keepdims=True)

    # 3. starts / re-polls
    start = cand & passed
    t0 = jnp.where(blocked & full_view,
                   jnp.maximum(row_unblock, ready), ready)
    # the single-sourced straggler model, traced into the kernel body
    dur = barrier_kernel.step_duration(u_dur_ref[...], ct_ref[...])
    event_time = jnp.where(start, t0 + dur, event_time)
    computing = computing | start
    fail = cand & ~passed
    blocked = (blocked | fail) & ~start
    sm_fail = fail & (sm_ref[...] != 0)
    ready = jnp.where(sm_fail, ready + poll, ready)
    event_time = jnp.where(sm_fail, ready, event_time)

    # 3b. adaptive-policy state updates — mirrors psp_tick_ref block 3b
    #     value-for-value (keepdims reductions instead of progress_gap's
    #     flat ones; same inputs, same clip/EMA arithmetic)
    if adaptive:
        mxs = jnp.max(jnp.where(alive, steps, _I32_MIN), axis=1,
                      keepdims=True)
        mns = jnp.min(jnp.where(alive, steps, _I32_MAX), axis=1,
                      keepdims=True)
        gap = jnp.where(_any(alive, 1), mxs - mns, 0)          # (W, 1)
        o_thr[...] = jnp.where(
            is_dssp & active,
            jnp.clip(gap, lo_ref[...], stal),
            thr_ref[...]).astype(i32)
        o_beta[...] = jnp.where(
            is_ann & active,
            jnp.clip(blo_ref[...] + gap - stal, blo_ref[...], beta),
            pbeta_ref[...]).astype(i32)
        al = eba_ref[...]                                      # (W, 1)
        o_ema[...] = jnp.where(is_ebsp & start,
                               (1.0 - al) * ema_ref[...] + al * dur,
                               ema_ref[...])

    o_steps[...] = steps
    o_alive[...] = alive.astype(i32)
    o_comp[...] = computing.astype(i32)
    o_event[...] = event_time
    o_ready[...] = ready
    o_block[...] = blocked.astype(i32)
    o_fin[...] = fin.astype(i32)
    o_start[...] = start.astype(i32)
    o_nfin[...] = jnp.sum(fin.astype(i32), axis=1, keepdims=True)


def psp_tick_tpu(state: Dict[str, jax.Array], rand: Dict[str, jax.Array],
                 params: Dict[str, jax.Array], t: jax.Array,
                 leave_n: jax.Array, join_n: jax.Array, *,
                 k_max: int, has_churn: bool, masked: bool,
                 adaptive: bool = False, interpret: bool = False,
                 ) -> Tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
    """Pallas tick: same contract as :func:`psp_tick_ref`.

    The kernel runs the control plane — grid = (⌈B/8⌉,), each step owns
    one :data:`KERNEL_ROWS`-row block's ``(8, P)`` state slices, its
    score tiles (or the shared tile when the whole batch reuses one
    draw) and its ``(8, 1)`` policy columns; batches pad up with inert
    rows (negative horizon).  Booleans travel as i32 and scores as the
    transposed ``[peer, decider]`` tile, padded to whole peer chunks.
    The data plane then runs as the reference's own XLA code
    (:func:`_data_plane`) on the kernel's ``fin``/``start`` masks.

    Raises:
      ValueError: at trace time, when ``P`` needs more VMEM than
        :data:`VMEM_BUDGET` (e.g. a 100k-node fleet) — the caller picks
        ``impl="ref"`` for such fleets explicitly.
    """
    B, P = state["steps"].shape
    i32, f32 = jnp.int32, jnp.float32
    use_u1 = k_max == 1 and not masked
    W = KERNEL_ROWS
    Bp = -(-B // W) * W
    need = _vmem_estimate(P, k_max, masked)
    if need > VMEM_BUDGET:
        raise ValueError(
            f"psp_tick kernel: P={P} nodes (k_max={k_max}, masked={masked})"
            f" needs ~{need >> 20} MiB of VMEM, over the "
            f"{VMEM_BUDGET >> 20} MiB budget; run this fleet with "
            "impl='ref'")
    chunk, Pp = _peer_chunking(P)

    def pad(a, fill=0):
        a = jnp.asarray(a)
        if Bp == B:
            return a
        filler = jnp.full((Bp - B,) + a.shape[1:], fill, a.dtype)
        return jnp.concatenate([a, filler], axis=0)

    def row(a, dtype):
        return pad(jnp.asarray(a).astype(dtype)), \
            pl.BlockSpec((W, P), lambda b: (b, 0))

    def col(a, dtype=i32, fill=0):
        return pad(jnp.asarray(a, dtype), fill).reshape(Bp, 1), \
            pl.BlockSpec((W, 1), lambda b: (b, 0))

    def scalar(a):
        return jnp.asarray(a, f32).reshape(1, 1), \
            pl.BlockSpec((1, 1), lambda b: (0, 0))

    inputs, specs = [], []

    def push(val_spec):
        inputs.append(val_spec[0])
        specs.append(val_spec[1])

    push(row(state["steps"], i32))
    for k in ("alive", "computing"):
        push(row(state[k], i32))
    push(row(state["event_time"], f32))
    push(row(state["ready"], f32))
    push(row(state["blocked"], i32))
    push(col(state["pend_leave"]))
    push(col(state["pend_join"]))
    push(col(leave_n))
    push(col(join_n))
    push(row(rand["dur"], f32))
    if k_max > 0:
        if use_u1:
            inputs.append(jnp.asarray(rand["u1"], f32).reshape(1, P))
            specs.append(pl.BlockSpec((1, P), lambda b: (0, 0)))
        else:
            sc_t = jnp.swapaxes(jnp.asarray(rand["scores"], f32), -1, -2)
            pad_peers = [(0, 0)] * (sc_t.ndim - 2) + [(0, Pp - P), (0, 0)]
            sc_t = jnp.pad(sc_t, pad_peers)
            if masked:
                inputs.append(pad(sc_t))
                specs.append(pl.BlockSpec((W, Pp, P), lambda b: (b, 0, 0)))
            else:
                inputs.append(sc_t)
                specs.append(pl.BlockSpec((Pp, P), lambda b: (0, 0)))
    if has_churn:
        push(row(rand["leave"], f32))
        push(row(rand["join"], f32))
    push(row(params["compute_time"], f32))
    push(row(params["valid_slot"], i32))
    push(col(params["staleness"]))
    push(col(params["beta_clip"]))
    push(col(params["is_asp"]))
    push(col(params["full_view"]))
    push(col(params["sampled"]))
    push(col(params["dist_hops"]))
    if adaptive:
        # policy-state/knob operands — pushed like the churn refs:
        # static batches never materialise them, so their kernel is the
        # exact pre-policy trace
        push(col(state["pol_thr"]))
        push(col(state["pol_beta"]))
        push(row(state["pol_ema"], f32))
        push(col(params["is_dssp"]))
        push(col(params["is_ebsp"]))
        push(col(params["is_anneal"]))
        push(col(params["pol_lo"]))
        push(col(params["beta_lo"]))
        push(col(params["ebsp_range"], f32))
        push(col(params["ebsp_alpha"], f32))
    # padded rows freeze: a negative horizon keeps them inert forever
    push(col(params["horizon"], f32, fill=-1.0))
    push(scalar(t))
    push(scalar(params["eps"]))
    push(scalar(params["poll"]))

    rp = lambda dt: jax.ShapeDtypeStruct((Bp, P), dt)
    cp = lambda: jax.ShapeDtypeStruct((Bp, 1), i32)
    row_spec = pl.BlockSpec((W, P), lambda b: (b, 0))
    col_spec = pl.BlockSpec((W, 1), lambda b: (b, 0))
    out_shape = [rp(i32), rp(i32), rp(i32), rp(f32), rp(f32), rp(i32),
                 cp(), cp(), rp(i32), rp(i32), cp(), cp()]
    out_specs = [row_spec] * 6 + [col_spec] * 2 + [row_spec] * 2 \
        + [col_spec] * 2
    if adaptive:
        out_shape += [cp(), rp(f32), cp()]      # pol_thr, pol_ema, pol_beta
        out_specs += [col_spec, row_spec, col_spec]

    extra = {} if interpret else dict(compiler_params=pltpu.CompilerParams(
        dimension_semantics=("parallel",),
        vmem_limit_bytes=min(max(2 * need, 32 << 20), 120 << 20)))
    outs = pl.pallas_call(
        functools.partial(_tick_kernel, k_max=k_max, has_churn=has_churn,
                          masked=masked, use_u1=use_u1, adaptive=adaptive,
                          W=W, P=P),
        grid=(Bp // W,),
        in_specs=specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="psp_tick",
        **extra,
    )(*inputs)

    outs = [o[:B] for o in outs]
    (steps, alive, computing, event_time, ready, blocked, pend_l, pend_j,
     fin, start, n_fin, ctrl) = outs[:12]
    fin, start = fin != 0, start != 0
    w, pulled = _data_plane(state, rand, params, fin, start)
    new_state = {"steps": steps, "alive": alive != 0,
                 "computing": computing != 0, "event_time": event_time,
                 "ready": ready, "blocked": blocked != 0,
                 "pend_leave": pend_l[:, 0], "pend_join": pend_j[:, 0],
                 "w": w, "pulled": pulled}
    if adaptive:
        pol_thr, pol_ema, pol_beta = outs[12:]
        new_state.update(pol_thr=pol_thr[:, 0], pol_ema=pol_ema,
                         pol_beta=pol_beta[:, 0])
    out = {"fin": fin, "start": start, "n_fin": n_fin[:, 0],
           "ctrl": ctrl[:, 0]}
    return new_state, out
