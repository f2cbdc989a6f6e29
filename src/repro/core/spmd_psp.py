"""TPU-native PSP: barrier control as a first-class SPMD training feature.

The paper's deployment model (WAN actors) does not exist on a TPU pod — an
SPMD program is lockstep by construction.  What transfers is the *semantics*:
workers at heterogeneous speeds, a server model updated by possibly-stale
pushes, and a barrier predicate (evaluated on a β-sample of step counters)
gating when each worker may start its next step.

This module implements those semantics as a single jittable train step
(`lax`-only control flow), so one SPMD program faithfully executes
BSP / SSP / ASP / pBSP / pSSP and their convergence-vs-virtual-wall-clock
trade-offs can be measured on real models — and so the PSP logic itself is
visible to the multi-pod dry-run and the roofline pipeline.

Mapping (DESIGN.md §3/§4):

* **worker** = a data-parallel shard group (the
  :data:`repro.parallel.sharding.PSP_WORKER_AXES` mesh axes carry the
  worker dimension W — ``data``, or (pod, data-row) pairs on a multi-pod
  mesh, resolved by :func:`repro.parallel.sharding.psp_worker_axes`; the
  ``model`` axis shards each worker's compute).  The server ``psum``
  reduces over exactly those axes, and the sweep engines' 2-D mesh
  (:mod:`repro.core.vector_sim_jax`) draws its ``rows``/``nodes`` names
  from the same vocabulary, so trainer and sweeps shard one way.
* **server model** = one replicated parameter pytree, updated by masked
  contributions (`psum` over the worker axis is the only cross-worker
  collective — identical schedule to plain DP, so PSP adds *zero* extra
  collective bytes on the data plane; the control plane is a W-length i32
  vector).
* **worker view** = each worker's stale pull of the server model (leading W
  axis sharded over ``data``), updated by a masked "pull" when the worker
  passes the barrier.  This reproduces read-my-writes staleness exactly.
* **virtual clock** = seeded per-worker step durations (heterogeneity +
  straggler injection, reproducing Fig 2 on-device).  Time advances
  event-style to the next completion.

The per-tick protocol (one call of :func:`psp_train_step`):

  1. every worker evaluates the loss on **its own view**,
  2. workers whose virtual clock completed *push*: the server applies the
     masked sum of their gradients through the optimizer,
  3. completed workers evaluate the barrier on a β-sample of the step
     vector; those allowed *pull* the fresh server model, bump their step,
     and draw the duration of their next local step; blocked workers hold
     (they re-sample next tick — the paper's "holds until condition is
     satisfied").

The masks decide what runs.  Which workers push depends on the virtual
clock alone, so it is known before any gradient exists: a tick on which
nobody pushes (a blocked worker polling while a straggler computes) runs
the forward pass only, for the reported loss, and skips the backward
pass, the server update and its optimizer state.  That gate is a scalar
``lax.cond`` outside the per-worker ``vmap`` (inside it a predicate would
lower to a select, which runs both branches).  The pull writes the server
model into the views of the allowed workers only, one in-place row write
each, so a tick on which nobody is allowed writes no view.  A gated tick
computes bit-for-bit the numbers of running every phase every tick.

Elastic worker sets (churn)
---------------------------
The paper's scalability claims assume a *dynamic* node population, and the
sweep engines model churn natively; with ``PSPConfig(churn=ChurnConfig(...))``
so does this trainer.  :class:`PSPState` carries a per-worker ``alive`` mask
plus pre-sampled Poisson leave/join schedules (the schedule machinery of
:func:`repro.core.vector_sim.sample_churn_schedules` — churn events are
data, not control flow), and every tick opens with a churn phase, all
``lax``-only so the step stays one SPMD program:

* a due **leave** kills a uniformly random alive worker (only while more
  than two are alive — the event engine's rule; the event is consumed
  either way).  The departed worker's counters freeze; it contributes
  **zero** gradient and zero bytes to the server ``psum`` (the push mask
  is alive-masked) and never gates a waiter (barrier predicates evaluate
  over alive workers only, via the masked
  :class:`~repro.core.barrier_kernel.BarrierKernel` predicates with
  β-samples drawn from alive peers).
* a due **join** revives a uniformly random departed slot: it is
  re-anchored with a *fresh pull* of the server model, restarts at the
  max alive step (the event engine's fresh-start rule), and decides this
  very tick.  Its never-computed gradient is masked out of the push.

At most one leave and one join fire per tick; surplus due events carry to
the next tick (cursor semantics — the Poisson totals are preserved, as
the sweep engines' ``pend_*`` counters).  Victim/joiner selection routes
through the shared :func:`repro.core.barrier_kernel.churn_victim` /
``churn_joiner`` rules, so trainer and simulators cannot silently
diverge; ``tests/test_elastic_equiv.py`` pins the cross-layer semantics
tick-for-tick and ``tests/test_spmd_psp.py`` holds a golden churn trace.
With ``churn=None`` the step consumes the identical RNG stream and
computes bit-for-bit the same numbers as the fixed-worker trainer.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.barrier_kernel import (BarrierKernel, BarrierPolicy,
                                       churn_joiner, churn_victim,
                                       make_policy)
from repro.core.barriers import BarrierControl, make_barrier

__all__ = ["ChurnConfig", "PSPConfig", "PSPState", "apply_external_churn",
           "elastic_drive", "external_drive", "linear_psp_state",
           "linear_psp_task", "psp_apply_tick", "psp_init",
           "psp_train_step", "make_psp_step_fn", "state_from_tree",
           "state_to_tree"]

PyTree = Any

_I32_MIN = jnp.iinfo(jnp.int32).min


@dataclasses.dataclass(frozen=True)
class ChurnConfig:
    """Elastic-worker-set (churn) configuration for the SPMD trainer.

    Leave/join events are two independent Poisson processes pre-sampled
    over ``horizon`` virtual seconds at :func:`psp_init` (the schedule
    machinery of :func:`repro.core.vector_sim.sample_churn_schedules`),
    so the jitted train step consumes them as fixed-shape data.  Past the
    horizon the worker set stays frozen at whatever population the
    schedule left behind.
    """

    leave_rate: float = 0.1        # workers leaving per virtual second
    join_rate: float = 0.1         # workers (re)joining per virtual second
    horizon: float = 120.0         # schedule length in virtual seconds
    seed: int = 0                  # schedule RNG seed (independent of init key)


@dataclasses.dataclass(frozen=True)
class PSPConfig:
    """Barrier-control configuration for the SPMD trainer."""

    barrier: str = "pssp"          # bsp|ssp|asp|pbsp|pssp|dssp|ebsp|ap(b|s)sp
    staleness: int = 4             # s (ignored by bsp/asp)
    sample_size: int = 16          # β (ignored by classic barriers)
    n_workers: int = 8             # W — data-parallel worker groups
    # heterogeneity model (virtual seconds per local step)
    base_compute: float = 0.1
    compute_jitter: float = 0.5    # per-step U[1−j/2, 1+j/2] noise
    straggler_frac: float = 0.0
    straggler_slowdown: float = 4.0
    poll_interval: float = 0.02    # blocked-worker re-sample cadence (virtual s)
    #: "mean" (pushing-worker mean), "sum", or "mean-alive" (divide by an
    #: EMA of the alive-worker count — contribution per worker stays
    #: stable when churn shrinks the pushing set; the PR-4 leftover)
    contribution: str = "mean"
    # adaptive-policy knobs (ignored by the five static barriers)
    staleness_lo: int = 0          # DSSP lower search bound r
    sample_size_lo: int = 1        # β-annealing lower bound β_min
    max_advance: int = 4           # Elastic-BSP max run-ahead R
    ema_alpha: float = 0.5         # Elastic-BSP duration-EMA α
    #: elastic worker set: None ⇒ fixed W workers (the pre-elastic trainer,
    #: bit-for-bit); a :class:`ChurnConfig` enables Poisson leave/join churn
    churn: Optional[ChurnConfig] = None

    def make_barrier(self) -> BarrierControl:
        """Instantiate the configured :class:`BarrierControl` policy."""
        return make_barrier(self.barrier, staleness=self.staleness,
                            sample_size=self.sample_size,
                            staleness_lo=self.staleness_lo,
                            sample_size_lo=self.sample_size_lo,
                            max_advance=self.max_advance,
                            ema_alpha=self.ema_alpha)

    @property
    def beta(self) -> int:
        """Effective sample size β (0 for classic/ASP barriers)."""
        b = self.make_barrier()
        return 0 if b.sample_size is None else min(b.sample_size,
                                                   self.n_workers - 1)

    @property
    def effective_staleness(self) -> int:
        """Staleness bound s after barrier-specific defaults apply."""
        b = self.make_barrier()
        return int(b.staleness)

    @property
    def is_classic(self) -> bool:
        """Classic barriers evaluate the full step vector (β = W−1)."""
        return self.barrier in ("bsp", "ssp")

    @property
    def is_asp(self) -> bool:
        """ASP never blocks (the barrier predicate is ⊤)."""
        return self.barrier == "asp"

    @property
    def has_churn(self) -> bool:
        """Whether the elastic churn phase is compiled into the step."""
        return self.churn is not None

    @property
    def barrier_kernel(self) -> BarrierKernel:
        """The unified barrier/straggler model this trainer executes.

        The same :class:`~repro.core.barrier_kernel.BarrierKernel`
        semantics drive the vectorized sweep engine, so trainer and
        simulator cannot silently diverge
        (``tests/test_barrier_kernel.py``).
        """
        return BarrierKernel(barrier=self.barrier,
                             staleness=self.effective_staleness,
                             beta=self.beta)

    @property
    def barrier_policy(self) -> BarrierPolicy:
        """The (possibly stateful) decision policy this trainer executes.

        Static barriers yield a stateless wrapper whose ``decide`` is
        exactly :meth:`barrier_kernel`'s predicate — the pre-policy
        trainer bit-for-bit.  Adaptive names (``dssp`` / ``ebsp`` /
        ``apbsp`` / ``apssp``) yield the stateful policy whose state
        pytree rides in :attr:`PSPState.policy`.
        """
        return make_policy(self.barrier, staleness=self.effective_staleness,
                           beta=self.beta, staleness_lo=self.staleness_lo,
                           beta_lo=self.sample_size_lo,
                           max_advance=self.max_advance,
                           ema_alpha=self.ema_alpha)


class PSPState(NamedTuple):
    """Replicated-or-sharded training state carried across ticks.

    The elastic fields (``alive`` through ``join_cursor``) are carried
    unconditionally so the pytree structure does not depend on the churn
    setting; with ``churn=None`` the mask is all-True and the schedules
    are empty, and the train step compiles to the fixed-worker program.
    """

    server_params: PyTree          # the single server model
    opt_state: PyTree              # optimizer state of the server model
    views: PyTree                  # [W, ...] worker views (stale pulls)
    step: jax.Array                # i32[W] logical step counters
    busy_until: jax.Array          # f32[W] virtual completion times
    pushed: jax.Array              # bool[W] pushed current step's update?
    now: jax.Array                 # f32[] virtual wall clock
    slow: jax.Array                # bool[W] straggler flags (static draw)
    key: jax.Array                 # PRNG key
    tick: jax.Array                # i32[] SPMD tick counter
    total_pushes: jax.Array        # i32[] server update count (Fig 1e)
    # ---- elastic worker set (PSPConfig.churn) ------------------------- #
    alive: jax.Array               # bool[W] current worker membership
    leave_times: jax.Array         # f32[El] pre-sampled leave schedule
    join_times: jax.Array          # f32[Ej] pre-sampled join schedule
    leave_cursor: jax.Array        # i32[] next unconsumed leave event
    join_cursor: jax.Array         # i32[] next unconsumed join event
    #: adaptive barrier-policy state (``cfg.barrier_policy.init``): empty
    #: for the five static barriers, so their pytree — and compiled
    #: program — is unchanged.  ``contribution="mean-alive"`` co-locates
    #: its alive-count EMA here under the ``"denom"`` key (policies pass
    #: unknown keys through untouched).
    policy: PyTree = {}


def _duration(cfg: PSPConfig, key: jax.Array, slow: jax.Array) -> jax.Array:
    """Seeded per-worker duration of one local step (virtual seconds).

    Routed through the unified straggler model
    (:func:`repro.core.barrier_kernel.step_duration`) — the same formula
    the sweep engine's grid tick applies, with the straggler slowdown
    folded into the per-worker base rate.
    """
    w = slow.shape[0]
    base = cfg.base_compute * jnp.where(slow, cfg.straggler_slowdown, 1.0)
    return BarrierKernel.step_duration(jax.random.uniform(key, (w,)), base,
                                       cfg.compute_jitter)


def psp_init(cfg: PSPConfig, params: PyTree, opt_init: Callable[[PyTree], PyTree],
             key: jax.Array) -> PSPState:
    """Build the initial PSP state from server params.

    With ``cfg.churn`` set, the Poisson leave/join schedules are
    pre-sampled here (from ``cfg.churn.seed`` via the shared
    :func:`repro.core.vector_sim.sample_churn_schedules` machinery — a
    numpy-side draw, so the jax init key stream is identical with and
    without churn) and carried in the state as fixed-shape arrays.
    """
    from repro.core.vector_sim import sample_churn_schedules

    w = cfg.n_workers
    views = jax.tree.map(lambda p: jnp.broadcast_to(p[None], (w,) + p.shape),
                         params)
    k_slow, k_dur, k_carry = jax.random.split(key, 3)
    n_slow = int(round(cfg.straggler_frac * w))
    slow = jnp.arange(w) < n_slow  # deterministic placement; permuted below
    slow = jax.random.permutation(k_slow, slow)
    dur = _duration(cfg, k_dur, slow)
    if cfg.has_churn:
        rng = np.random.default_rng(cfg.churn.seed)
        lt, jt = sample_churn_schedules(rng, cfg.churn.leave_rate,
                                        cfg.churn.join_rate,
                                        cfg.churn.horizon)
    else:
        lt = jt = np.empty(0)
    policy = dict(cfg.barrier_policy.init(w))
    if cfg.contribution == "mean-alive":
        policy["denom"] = jnp.asarray(float(w), jnp.float32)
    return PSPState(
        server_params=params,
        opt_state=opt_init(params),
        views=views,
        step=jnp.zeros((w,), jnp.int32),
        busy_until=dur,
        pushed=jnp.zeros((w,), bool),
        now=jnp.zeros((), jnp.float32),
        slow=slow,
        key=k_carry,
        tick=jnp.zeros((), jnp.int32),
        total_pushes=jnp.zeros((), jnp.int32),
        alive=jnp.ones((w,), bool),
        leave_times=jnp.asarray(lt, jnp.float32),
        join_times=jnp.asarray(jt, jnp.float32),
        leave_cursor=jnp.zeros((), jnp.int32),
        join_cursor=jnp.zeros((), jnp.int32),
        policy=policy,
    )


def _barrier_allowed(cfg: PSPConfig, key: jax.Array, step: jax.Array,
                     alive: Optional[jax.Array] = None) -> jax.Array:
    """bool[W]: may each worker start its next step, per the barrier?

    Delegates to the unified barrier model
    (:meth:`PSPConfig.barrier_kernel`): full-view masked-min for BSP/SSP,
    a β-sample through the shared sampling primitive for pBSP/pSSP, ⊤ for
    ASP — exactly the predicate the sweep engine's fused tick evaluates.
    Under churn, ``alive`` masks both the full-view minimum and the
    β-sample pool (``sample_alive_peer_indices_jax``): departed workers'
    frozen counters never gate waiters, and samples draw alive peers only.
    """
    return cfg.barrier_kernel.allowed(key, step, alive)


def _schedule_due(times: jax.Array, cursor: jax.Array,
                  now: jax.Array) -> jax.Array:
    """bool[]: is the next unconsumed schedule event at or before ``now``?"""
    n = times.shape[0]
    if n == 0:
        return jnp.zeros((), bool)
    return (cursor < n) & (times[jnp.minimum(cursor, n - 1)] <= now)


def _membership_update(state: PSPState, leave_sel: jax.Array,
                       join_sel: jax.Array) -> PSPState:
    """Apply membership-change masks to the state (the churn kernel).

    ``leave_sel`` / ``join_sel`` are bool[W] selections of workers leaving
    and (re)joining *this instant*.  Leavers' counters freeze where they
    are.  Joiners follow the engines' fresh-start rule: they are
    re-anchored with a fresh pull of the server model, restart at the max
    alive step (evaluated after both masks land, so a rejoining
    front-runner's own frozen counter participates — the
    :func:`_fire_churn` ordering, preserved bit-for-bit), become
    completed (``busy_until = now``) so they decide this very tick, and
    have ``pushed`` set so a gradient computed while dead can never land.

    This is the single definition of "what a leave/join does to trainer
    state": the schedule-driven Poisson phase (:func:`_fire_churn`) and
    the process-driven cluster harness (:func:`apply_external_churn`)
    both route through it, so simulated and real churn cannot silently
    diverge.  Cursor bookkeeping is the caller's job.
    """
    alive = (state.alive & ~leave_sel) | join_sel
    fresh = jnp.max(jnp.where(alive, state.step, _I32_MIN))
    step = jnp.where(join_sel, fresh, state.step)

    def _reanchor(view, p):
        m = join_sel.reshape((-1,) + (1,) * p.ndim)
        return jnp.where(m, p[None], view)

    return state._replace(
        views=jax.tree.map(_reanchor, state.views, state.server_params),
        step=step,
        busy_until=jnp.where(join_sel, state.now, state.busy_until),
        pushed=state.pushed | join_sel,
        alive=alive,
    )


def _fire_churn(cfg: PSPConfig, state: PSPState,
                k_churn: jax.Array) -> PSPState:
    """Phase 0 of an elastic tick: fire due leave/join events (≤ 1 each).

    Semantics follow the sweep engines' churn rules (pinned by
    ``tests/test_elastic_equiv.py``): a leave kills a uniformly random
    alive worker only while more than two are alive, a join revives a
    uniformly random departed slot at the current max alive step and lets
    it decide this tick.  Due events are consumed (cursor advances) even
    when the population guard skips the effect — Poisson totals are
    preserved; several same-tick events drain one per tick, the fused
    tick's ``pend_*`` carry rule (the numpy grid engine instead drains
    same-tick surpluses within the tick — a timing difference of rare
    multi-event ticks, not a protocol difference).  The membership
    effect itself (joiner fresh-start/re-anchor/push-mask semantics)
    lives in :func:`_membership_update`; this phase only decides *who*.
    """
    w = cfg.n_workers
    iota = jnp.arange(w)
    k_leave, k_join = jax.random.split(k_churn)
    alive = state.alive

    # leave: kill a uniformly random alive worker (population floor: 2)
    due_l = _schedule_due(state.leave_times, state.leave_cursor, state.now)
    do_l = due_l & (jnp.sum(alive) > 2)
    victim = churn_victim(jax.random.uniform(k_leave, (w,)), alive)
    leave_sel = do_l & (iota == victim)
    alive = alive & ~leave_sel

    # join: revive a uniformly random departed slot, fresh-started
    due_j = _schedule_due(state.join_times, state.join_cursor, state.now)
    do_j = due_j & jnp.any(~alive)
    joiner = churn_joiner(jax.random.uniform(k_join, (w,)), alive)
    join_sel = do_j & (iota == joiner)

    state = _membership_update(state, leave_sel, join_sel)
    return state._replace(
        leave_cursor=state.leave_cursor + due_l.astype(jnp.int32),
        join_cursor=state.join_cursor + due_j.astype(jnp.int32),
    )


def apply_external_churn(cfg: PSPConfig, state: PSPState, *,
                         leave: Tuple[int, ...] = (),
                         join: Tuple[int, ...] = ()) -> PSPState:
    """Apply *observed* membership changes (real process churn) to state.

    The cluster harness (:mod:`repro.launch.cluster`) maps actual worker
    deaths and rejoins onto the elastic trainer's alive-mask machinery
    through this function: a SIGKILLed worker is a ``leave``, a respawned
    worker that restored the latest snapshot is a ``join``.  Both apply
    the exact :func:`_membership_update` kernel the Poisson churn phase
    fires, so a real death behaves bit-for-bit like a scheduled one.

    Unlike :func:`_fire_churn` there is no population floor and no
    one-event-per-tick drain: real deaths are observed facts, not
    schedule draws, and a correlated rack-level kill takes several
    workers in one call.  Leaving an already-dead worker and joining an
    already-alive one are no-ops (idempotent re-application).  The churn
    RNG stream is untouched — this is host-driven, between ticks, and
    composes with ``churn=None`` configs (the cluster's case).
    """
    w = cfg.n_workers
    alive = np.asarray(state.alive)
    leave_sel = np.zeros(w, bool)
    for i in leave:
        leave_sel[int(i)] = True
    leave_sel &= alive                       # no-op on dead workers
    join_sel = np.zeros(w, bool)
    for i in join:
        join_sel[int(i)] = True
    join_sel &= ~(alive & ~leave_sel)        # no-op on alive workers
    if not leave_sel.any() and not join_sel.any():
        return state
    return _membership_update(state, jnp.asarray(leave_sel),
                              jnp.asarray(join_sel))


def psp_apply_tick(
    cfg: PSPConfig,
    opt_update: Callable[[PyTree, PyTree, PyTree], Tuple[PyTree, PyTree]],
    state: PSPState,
    compute: Callable[[PSPState], Tuple[jax.Array, PyTree]],
) -> Tuple[PSPState, dict]:
    """One SPMD tick of PSP, with the gradient source abstracted out.

    ``compute(state) -> (losses, grads)`` supplies the f32[W] losses and
    [W, ...] gradient pytree, evaluated *after* the churn phase (so a
    same-tick joiner's gradient comes from its re-anchored view, as it
    always did).  :func:`psp_train_step` passes the vmapped in-process
    ``grad_fn``; the multi-process cluster coordinator
    (:mod:`repro.launch.cluster`) passes the gradients its worker
    subprocesses pushed over the bus (zeros in non-pushing rows — the
    push mask discards those columns identically either way, which is
    what makes the cluster bit-exact against the in-process trainer).

    ``compute`` runs inside a ``lax.cond`` on "some worker pushes": the
    update branch uses its gradients, the hold branch only its losses, so
    there the gradients are dead code the compiler drops (the in-process
    backward pass; the cluster's gradients arrive computed either way).
    The pull loops over the allowed workers only and writes the server
    model into each one's view in place.  ``metrics["updated"]`` and
    ``metrics["pulled"]`` say whether the update ran and whether any view
    was pulled.

    Each phase runs under a device scope (``jax.named_scope``) that a
    profiler trace reads back: ``train.barrier`` (churn, the barrier
    decision, the clock advance, the metrics), ``train.optimizer`` (the
    masked server update) and ``train.pull`` (the views' pull).  The
    gradients are ``compute``'s; the trainers scope them ``train.grads``.

    Returns: (new_state, metrics)
    """
    with jax.named_scope("train.barrier"):
        if cfg.has_churn:
            # (0) elastic churn phase: fire due pre-sampled leave/join events.
            # The extra key split is compiled in only when churn is enabled,
            # so the churn=None RNG stream is identical to the fixed-worker
            # trainer (bit-for-bit on golden/regression tests).
            key, k_bar, k_dur, k_churn = jax.random.split(state.key, 4)
            state = _fire_churn(cfg, state, k_churn)
        else:
            key, k_bar, k_dur = jax.random.split(state.key, 3)
        alive = state.alive
        # completions push to the server; departed workers are masked out
        # of the psum — zero gradient, zero bytes.  The mask reads the clock
        # alone, so it decides before any gradient exists whether the
        # server update runs at all.
        completed = state.busy_until <= state.now
        push_mask = completed & ~state.pushed & alive
        any_push = jnp.any(push_mask)
        denom = jnp.maximum(jnp.sum(push_mask), 1)

    def _update(st):
        # (1) every worker computes on its own (possibly stale) view,
        # (2) the pushing workers' masked sum goes through the optimizer
        losses, grads = compute(st)
        with jax.named_scope("train.optimizer"):
            if cfg.contribution == "mean-alive":
                # churn-aware scaling: divide by the carried alive-count EMA,
                # not by this tick's pushing-set size — per-worker
                # contribution stays stable as churn shrinks/grows the
                # population.  Reads the OLD state (the EMA update lands
                # below with the policy state).
                scale = 1.0 / jnp.maximum(st.policy["denom"], 1.0)
            else:
                scale = jnp.where(cfg.contribution == "mean", 1.0 / denom,
                                  1.0)

            def _masked_sum(g):
                m = push_mask.reshape((-1,) + (1,) * (g.ndim - 1))
                return jnp.sum(jnp.where(m, g, 0), axis=0) * scale

            server_grad = jax.tree.map(_masked_sum, grads)
            updates, new_opt = opt_update(server_grad, st.opt_state,
                                          st.server_params)
            new_params = jax.tree.map(jnp.add, st.server_params, updates)
        return losses, new_params, new_opt

    def _hold(st):
        # no worker pushes: only the losses are read, so the gradients (and
        # with them the backward pass) are dead code the compiler drops
        losses, _ = compute(st)
        return losses, st.server_params, st.opt_state

    with jax.named_scope("train.optimizer"):
        losses, new_params, new_opt = jax.lax.cond(any_push, _update, _hold,
                                                   state)
        pushed = state.pushed | push_mask

    with jax.named_scope("train.barrier"):
        # (3) barrier: completed alive workers try to start their next step.
        # The next-step duration is drawn *before* the decide so adaptive
        # policies (Elastic-BSP's duration EMA) can observe it; k_dur and
        # k_bar are independent splits of the same parent key, so hoisting
        # the draw leaves every RNG stream bit-identical.  For static
        # barriers ``decide`` is exactly the old ``_barrier_allowed``
        # predicate and passes the (empty) policy state through.
        next_dur = _duration(cfg, k_dur, state.slow)
        allowed, new_policy = cfg.barrier_policy.decide(
            state.policy, k_bar, state.step, next_dur,
            alive if cfg.has_churn else None)
        allowed = allowed & completed & alive
        new_step = state.step + allowed.astype(jnp.int32)
        new_busy = jnp.where(allowed, state.now + next_dur, state.busy_until)
        new_pushed = jnp.where(allowed, False, pushed)

    with jax.named_scope("train.pull"):
        # allowed workers pull the fresh server model: one in-place write
        # of it into each allowed worker's view, in index order (a select
        # over all W views would rewrite every view on every tick)
        n_pull = jnp.sum(allowed)
        order = jnp.argsort(~allowed, stable=True)

        def _body(carry):
            k, views = carry
            i = order[k]
            views = jax.tree.map(
                lambda v, p: jax.lax.dynamic_update_index_in_dim(v, p, i, 0),
                views, new_params)
            return k + 1, views

        _, new_views = jax.lax.while_loop(lambda c: c[0] < n_pull, _body,
                                          (jnp.int32(0), state.views))

    with jax.named_scope("train.barrier"):
        if cfg.contribution == "mean-alive":
            new_policy = dict(new_policy)
            new_policy["denom"] = (0.9 * state.policy["denom"]
                                   + 0.1 * jnp.sum(alive).astype(jnp.float32))

        # (4) event-driven virtual-time advance: jump to the earlier of (a) the
        # next completion of a still-busy alive worker, (b) the next poll of a
        # barrier-blocked worker (the paper's "holds until condition is
        # satisfied" — re-sampling costs a poll interval of virtual time).
        # Departed workers' frozen clocks never hold time back; with at least
        # two alive workers every tick either has someone busy or someone
        # polling, so the clock always advances and pending joins fire.
        blocked = completed & ~allowed & alive
        next_busy = jnp.min(jnp.where((new_busy > state.now) & alive, new_busy,
                                      jnp.inf))
        next_poll = jnp.where(jnp.any(blocked),
                              state.now + cfg.poll_interval, jnp.inf)
        next_time = jnp.minimum(next_busy, next_poll)
        new_now = jnp.where(jnp.isfinite(next_time),
                            jnp.maximum(state.now, next_time), state.now)

        new_state = state._replace(
            server_params=new_params,
            opt_state=new_opt,
            views=new_views,
            step=new_step,
            busy_until=new_busy,
            pushed=new_pushed,
            now=new_now,
            key=key,
            tick=state.tick + 1,
            total_pushes=state.total_pushes + jnp.sum(push_mask),
            policy=new_policy,
        )
        if cfg.has_churn:
            # progress statistics over the *current* worker set only — a
            # departed straggler's frozen counter is not progress
            n_alive = jnp.maximum(jnp.sum(alive), 1)
            mean_step = (jnp.sum(jnp.where(alive, new_step, 0))
                         / n_alive.astype(jnp.float32))
            alive_steps_max = jnp.max(jnp.where(alive, new_step, _I32_MIN))
            alive_steps_min = jnp.min(
                jnp.where(alive, new_step, jnp.iinfo(jnp.int32).max))
            step_spread = alive_steps_max - alive_steps_min
        else:
            mean_step = jnp.mean(new_step.astype(jnp.float32))
            step_spread = jnp.max(new_step) - jnp.min(new_step)
        metrics = {
            # pushed-worker mean; falls back to the all-worker mean on ticks
            # where nobody completed (avoids misleading 0.0 readouts)
            "loss": jnp.where(any_push,
                              jnp.sum(jnp.where(push_mask, losses, 0)) / denom,
                              jnp.mean(losses)),
            "pushes": jnp.sum(push_mask),
            # which of the tick's gated phases ran: the server update (some
            # worker pushed) and the views' pull (some worker was allowed)
            "updated": any_push.astype(jnp.int32),
            "pulled": (n_pull > 0).astype(jnp.int32),
            "allowed": jnp.sum(allowed),
            "blocked": jnp.sum(blocked),
            "alive": jnp.sum(alive),
            "mean_step": mean_step,
            "step_spread": step_spread,
            "virtual_time": new_now,
        }
    return new_state, metrics


def psp_train_step(
    cfg: PSPConfig,
    grad_fn: Callable[[PyTree, PyTree], Tuple[jax.Array, PyTree]],
    opt_update: Callable[[PyTree, PyTree, PyTree], Tuple[PyTree, PyTree]],
    state: PSPState,
    batch: PyTree,
) -> Tuple[PSPState, dict]:
    """One SPMD tick of PSP training (in-process gradients).

    Args:
      cfg: barrier configuration (static).
      grad_fn: ``(params, microbatch) -> (loss, grads)`` for ONE worker;
        vmapped over the leading W axis of ``state.views`` / ``batch``.
      opt_update: ``(grads, opt_state, params) -> (updates, new_opt_state)``.
      state: carried :class:`PSPState`.
      batch: pytree with leading axis W (per-worker microbatches).

    A thin wrapper over :func:`psp_apply_tick` that computes the
    gradients in-process by vmapping ``grad_fn`` over the worker views —
    pure code motion from the pre-cluster trainer, so every golden trace
    and RNG stream is bit-identical.

    Returns: (new_state, metrics)
    """
    return psp_apply_tick(cfg, opt_update, state,
                          lambda st: jax.vmap(grad_fn)(st.views, batch))


def state_to_tree(state: PSPState) -> dict:
    """The checkpointable pytree of the FULL training state.

    A plain field-name → value dict (``NamedTuple._asdict``), so the
    archive keys read ``server_params/...``, ``opt_state/...``, ``step``,
    ``key`` … — every leaf the trainer carries, including the optimizer
    state, worker views, step/busy/pushed/alive arrays, churn schedules
    and cursors, the adaptive-policy pytree and the RNG key.  Persisting
    this tree (not just ``server_params``) is what makes kill-and-resume
    bit-exact: restoring it and replaying the same minibatch stream
    reproduces the uninterrupted run's numbers leaf for leaf.
    """
    return state._asdict()


def state_from_tree(tree: dict) -> PSPState:
    """Inverse of :func:`state_to_tree` (e.g. on a restored checkpoint)."""
    return PSPState(**tree)


def make_psp_step_fn(cfg: PSPConfig, grad_fn, opt_update):
    """Convenience: partially-applied, jit-ready step function."""
    return functools.partial(psp_train_step, cfg, grad_fn, opt_update)


def linear_psp_task(dim: int, lr: float = 0.1, seed: int = 0):
    """The paper's linear-regression task, packaged for this trainer.

    One definition serves every consumer that trains the trainer on the
    paper's evaluation workload — the churn benchmark
    (:mod:`benchmarks.churn_bench`), the elastic demo
    (``examples/elastic_train.py``) and the trainer/equivalence test
    suites — so "which task do the elastic numbers measure" has exactly
    one answer.

    Returns:
      (w_true, grad_fn, opt_update): the ground-truth vector f32[dim], a
      per-worker ``(params, (x, y)) -> (loss, grads)`` for params pytree
      ``{"w": f32[dim]}``, and a plain-SGD ``opt_update`` with step size
      ``lr``.
    """
    w_true = jax.random.normal(jax.random.PRNGKey(seed), (dim,)) \
        / np.sqrt(dim)

    def grad_fn(params, batch):
        # the mean as a sequential (cumulative) sum: XLA's CPU reduction
        # vectorizes a batched (vmapped) row differently from a solo one,
        # which would move the loss by an ulp between the two
        x, y = batch
        return jax.value_and_grad(
            lambda p: jnp.cumsum((x @ p["w"] - y) ** 2)[-1] / y.shape[-1]
        )(params)

    def opt_update(g, s, p):
        return jax.tree.map(lambda gi: -lr * gi, g), s

    return w_true, grad_fn, opt_update


def linear_psp_state(cfg: PSPConfig, dim: int,
                     init_seed: int = 1) -> PSPState:
    """The initial :class:`PSPState` of :func:`elastic_drive`'s run.

    Exposed separately because it doubles as the *restore template*: a
    checkpoint written mid-drive restores into this state's structure
    (same shapes/dtypes by construction), which is how the elastic demo
    and the resume tests rebuild a killed run.
    """
    return psp_init(cfg, {"w": jnp.zeros((dim,))}, lambda p: None,
                    jax.random.PRNGKey(init_seed))


def elastic_drive(cfg: PSPConfig, dim: int, ticks: int, *, batch: int = 16,
                  lr: float = 0.1, task_seed: int = 0, init_seed: int = 1,
                  batch_seed: int = 2, state: Optional[PSPState] = None,
                  start_tick: int = 0):
    """Drive the trainer on the linear task; the canonical tick loop.

    One definition of "init the trainer, jit the step, feed random
    minibatches for N ticks" shared by the churn benchmark
    (:mod:`benchmarks.churn_bench`), the elastic demo
    (``examples/elastic_train.py``) and the trainer test suites, so their
    trajectories are the same run by construction (the golden churn trace
    pins this loop's exact RNG consumption).

    Resume: pass a restored ``state`` plus the ``start_tick`` it was
    checkpointed at and the drive fast-forwards the minibatch key stream
    (``start_tick`` splits, no data materialized) before continuing —
    ticks ``start_tick..ticks-1`` then consume exactly the keys the
    uninterrupted run would have, so the resumed trajectory is
    bit-identical (``tests/test_checkpoint.py``).

    Returns:
      (w_true, it): the task ground truth and an iterator yielding one
      ``(state, metrics)`` pair per tick (the state *after* that tick).
    """
    w_true, grad_fn, opt_update = linear_psp_task(dim, lr=lr, seed=task_seed)
    if state is None:
        state = linear_psp_state(cfg, dim, init_seed)
    step = jax.jit(make_psp_step_fn(cfg, grad_fn, opt_update))

    def _ticks(state, kb):
        for _ in range(start_tick):          # replay the consumed key stream
            kb, _ = jax.random.split(kb)
        for _ in range(start_tick, ticks):
            kb, k1 = jax.random.split(kb)
            x = jax.random.normal(k1, (cfg.n_workers, batch, dim))
            state, m = step(state, (x, x @ w_true))
            yield state, m

    return w_true, _ticks(state, jax.random.PRNGKey(batch_seed))


def external_drive(cfg: PSPConfig, dim: int, ticks: int,
                   events: dict, *, batch: int = 16, lr: float = 0.1,
                   task_seed: int = 0, init_seed: int = 1,
                   batch_seed: int = 2):
    """:func:`elastic_drive` with an *explicit* leave/join schedule.

    ``events`` maps ``tick -> (leave_ids, join_ids)``; each entry is
    applied via :func:`apply_external_churn` immediately before that
    tick's train step, exactly where the cluster coordinator applies
    observed process churn.  With ``cfg.churn=None`` this is the
    single-process reference for a multi-process cluster run: replaying
    the cluster's recorded membership events here must reproduce the
    cluster's server params bit-for-bit (same alive trajectory, same RNG
    stream, same pushes — ``tests/test_cluster_faults.py`` pins it).

    Returns:
      (w_true, it): ground truth and a per-tick ``(state, metrics)``
      iterator, mirroring :func:`elastic_drive`.
    """
    w_true, grad_fn, opt_update = linear_psp_task(dim, lr=lr, seed=task_seed)
    state = linear_psp_state(cfg, dim, init_seed)
    step = jax.jit(make_psp_step_fn(cfg, grad_fn, opt_update))

    def _ticks(state, kb):
        for t in range(ticks):
            if t in events:
                leave, join = events[t]
                state = apply_external_churn(cfg, state, leave=tuple(leave),
                                             join=tuple(join))
            kb, k1 = jax.random.split(kb)
            x = jax.random.normal(k1, (cfg.n_workers, batch, dim))
            state, m = step(state, (x, x @ w_true))
            yield state, m

    return w_true, _ticks(state, jax.random.PRNGKey(batch_seed))
