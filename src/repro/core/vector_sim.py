"""Vectorized batched sweep engine — the evaluation layer's fast path.

The paper's headline results (Figs 1–3) are *sweeps*: barrier policy ×
straggler fraction × slowness × system size × seed.  The discrete-event
:class:`~repro.core.simulator.Simulator` processes one Python event at a
time, so a full scenario matrix costs minutes; this module advances **all P
nodes and a batch of B configurations simultaneously** on a fixed time
grid, cutting sweep wall-clock by an order of magnitude while keeping the
event-driven simulator as the semantic reference
(``tests/test_vector_sim.py`` and ``tests/test_vector_sim_jax.py`` hold the
distribution-level equivalence suites).

Sweep API
---------
:func:`run_sweep` is the entry point::

    from repro.core.simulator import SimConfig
    from repro.core.vector_sim import run_sweep

    configs = [SimConfig(barrier=make_barrier(b), straggler_frac=f, seed=s)
               for b in ("bsp", "pbsp") for f in (0.0, 0.1) for s in range(4)]
    results = run_sweep(configs)                  # NumPy grid engine
    results = run_sweep(configs, backend="jax")   # jit + lax.scan engine

Configurations are grouped by structural key (``n_nodes``, ``dim``,
``batch``, ``duration``, ``measure_interval``, ``poll_interval``, churn
on/off); each group runs as one batched :class:`VectorSimulator`,
everything else (seed, learning rate, straggler settings, barrier policy,
noise, distributed sampling, churn rates) is batched per-row.  Results come
back in input order regardless of backend or grouping.

Backend matrix
--------------
===========  ==========================  ==========================
backend      no churn                    churn (alive-masked rows)
===========  ==========================  ==========================
``numpy``    array ops per grid tick     same + per-tick event batch
``jax``      donated chunked scans       same, per-row masked samples
===========  ==========================  ==========================

Both backends handle churn natively — nothing falls back to the event
engine.  The jax backend is device-resident: each grid tick — control
plane (churn, finish bookkeeping, barrier decisions, start/re-poll)
*and* data plane (masked SGD push, model-view pull) — runs as one fused
kernel, the Pallas tick of :mod:`repro.kernels.psp_tick` on TPU, its
jnp twin on CPU, driven by donated chunked scans sharded over a 1-D
device mesh (:mod:`repro.core.vector_sim_jax`, schedule chosen by
:mod:`repro.core.sweep_plan`), with β-samples from the shared
:mod:`repro.core.sampling` primitives and barrier/straggler semantics
single-sourced in :mod:`repro.core.barrier_kernel` (the same model the
SPMD trainer uses).  The jax backend additionally merges structural
groups that differ in ``n_nodes``, churn-ness or duration (ragged P
padded with permanently-dead alive-mask slots; shorter rows freeze at
their own horizon), so a mixed sweep compiles once per
(dim, batch, cadence) shape; see ``docs/ARCHITECTURE.md`` for the full
engine map.

Simulation model (one grid tick of width ``dt``)
------------------------------------------------
0. **Churn** — pre-sampled Poisson leave/join events due this tick fire:
   a leave kills a uniformly random alive node (only while more than two
   are alive, as the event engine), a join revives a dead node at the
   current max alive step and lets it decide this tick.  Departed nodes neither finish nor decide;
   the full-view minimum is re-derived from the alive-masked step matrix
   every tick, so a departed global-min straggler unblocks waiters on the
   next tick — the grid analogue of the event engine's ``_on_leave`` wake.
1. **Finish** — nodes whose busy-until clock expired push their update
   (gradient of the linear task at their *pulled* model — SGD updates
   commute within a tick because each depends only on the puller's stale
   view), advance their step counter, and become *deciding*.
2. **Decide** — all deciding nodes evaluate their barrier predicate in one
   masked batch: ASP rows always pass; full-view rows (BSP/SSP) pass iff
   ``step − min(alive steps) ≤ staleness``; sampled rows (pBSP/pSSP) draw β
   **alive** peers without replacement, excluding themselves (the
   worker-centric semantics of paper §6.4, matching
   ``sample_steps_jax(..., exclude_self=True)``) and pass iff no sampled
   peer lags more than ``staleness`` behind.
3. **Start** — passing nodes pull the server model and draw their next
   step duration, anchored at their *continuous* ready time (not the grid
   tick), so grid quantisation does not systematically slow progress.
   Blocked sampled rows re-poll after ``poll_interval`` exactly like the
   event simulator; blocked full-view rows re-check every tick (the grid
   analogue of the event simulator's min-moved wakeup).
4. **Measure** — error/update traces are recorded on the same
   ``measure_interval`` grid as :class:`SimResult` expects.

Determinism: a sweep is deterministic given the config list and backend
(the batch shares one dynamics RNG seeded from all row seeds), and each
row's *static* draw — ground-truth model, node speeds, straggler
assignment — replays the event simulator's per-seed init stream exactly
(:func:`repro.core.simulator.draw_static_state`) on **both** backends.
Per-row dynamics noise (minibatches, step-duration jitter, β-samples,
churn victims) is shared across the batch, so a row's trajectory matches
the event simulator at the distribution level (mean progress, lag pmf
shape, final error), not sample-path level; the numpy and jax backends
likewise agree at the distribution level (different dynamics streams) —
``tests/test_vector_sim_jax.py`` pins per-backend golden traces.
"""
from __future__ import annotations

import contextlib
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.barriers import ASP
from repro.core.simulator import (SimConfig, SimResult, draw_static_state,
                                  sample_poisson_times)

__all__ = ["VectorSimulator", "run_sweep", "sample_churn_schedules",
           "BACKENDS"]

_EPS = 1e-9

BACKENDS = ("numpy", "jax")


def _group_key(cfg: SimConfig) -> Tuple:
    """Structural fields that must agree within one numpy batch.

    Churn-ness is structural: churn batches carry alive masks and per-row
    event schedules, and both backends specialise their tick on it
    (per-row masked sampling vs the shared-index fast path).
    """
    has_churn = cfg.churn_join_rate > 0.0 or cfg.churn_leave_rate > 0.0
    return (cfg.n_nodes, cfg.dim, cfg.batch, float(cfg.duration),
            float(cfg.measure_interval), float(cfg.poll_interval), has_churn)


def _merge_key(cfg: SimConfig) -> Tuple:
    """Relaxed jax grouping key: ragged P, churn-ness and duration merge.

    The jax backend pads heterogeneous ``n_nodes`` up to the group max and
    runs the merged batch as **one** chunked scan schedule — padded node
    slots are permanently dead alive-mask entries — so a ragged sweep
    costs one compile per bucket instead of one per structural shape.  P
    is bucketed to the next power of two: that caps the padding waste of
    any row at 2× (4× on the P² sampling terms) while still collapsing
    the near-size shapes a scalability sweep produces.  Durations merge
    too: the tick grid runs to the group maximum and each row freezes at
    its own horizon (the fused tick's ``active`` gate), with the chunk
    runner early-exiting once every row is done.  Only the fields that
    fix the tick/measurement cadence and the data-plane shapes must
    still agree exactly.
    """
    p_bucket = 1 << max(0, cfg.n_nodes - 1).bit_length()
    return (p_bucket, cfg.dim, cfg.batch,
            float(cfg.measure_interval), float(cfg.poll_interval))


def sample_churn_schedules(rng: np.random.Generator, leave_rate: float,
                           join_rate: float, duration: float
                           ) -> Tuple[np.ndarray, np.ndarray]:
    """Pre-sample one row's Poisson churn schedule: (leave, join) times.

    The batched engines and the elastic SPMD trainer
    (:mod:`repro.core.spmd_psp`) all consume churn as *pre-sampled*
    schedules rather than on-line exponential re-arming, so churn events
    are data, not control flow — a fixed-shape input a ``lax.scan`` (or a
    jitted train step) can carry.  Both processes are the event
    simulator's model (:func:`repro.core.simulator.sample_poisson_times`),
    drawn leave-first from ``rng`` so a shared generator yields a
    deterministic schedule.
    """
    leaves = sample_poisson_times(rng, leave_rate, duration)
    joins = sample_poisson_times(rng, join_rate, duration)
    return leaves, joins


class VectorSimulator:
    """Batched fixed-grid simulator over B same-shape configurations."""

    def __init__(self, configs: Sequence[SimConfig],
                 dt: Optional[float] = None, backend: str = "numpy"):
        if not configs:
            raise ValueError("empty config batch")
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; "
                             f"choose from {BACKENDS}")
        keys = {_group_key(c) for c in configs}
        if len(keys) > 1 and (backend != "jax"
                              or len({_merge_key(c) for c in configs}) > 1):
            raise ValueError(f"heterogeneous batch: {keys} "
                             "(use run_sweep, which groups automatically; "
                             "only the jax backend batches ragged P/churn)")
        self.configs = list(configs)
        self.backend = backend
        B = len(configs)
        c0 = configs[0]
        #: per-row true population; P is the padded batch width (jax only
        #: — the numpy engine always runs structurally homogeneous batches)
        self.n_true = np.array([c.n_nodes for c in configs], dtype=np.int64)
        P, d = int(self.n_true.max()), c0.dim
        self.B, self.P, self.d, self.batch = B, P, d, c0.batch
        #: per-row horizon; the shared grid runs to the batch max and the
        #: jax tick freezes each row past its own duration (merged
        #: durations are a jax-only grouping — numpy batches are strict)
        self.row_duration = np.array([float(c.duration) for c in configs])
        self.duration = float(self.row_duration.max())
        self.poll_interval = float(c0.poll_interval)
        self.measure_interval = float(c0.measure_interval)
        self.dt = float(dt) if dt is not None else self.poll_interval
        if self.dt > self.poll_interval + 1e-12:
            # a node can finish/decide at most once per tick, so a coarse
            # grid silently caps throughput and skips poll attempts —
            # results would be wrong, not just coarse
            raise ValueError(
                f"dt={self.dt} must not exceed poll_interval="
                f"{self.poll_interval}")
        self.has_churn = any(c.churn_join_rate > 0.0
                             or c.churn_leave_rate > 0.0 for c in configs)

        # ---- per-row static state: replay the event simulator's init ---- #
        #: ragged padding mask: slot p exists in row b iff p < n_true[b]
        self.valid_slot = np.arange(P) < self.n_true[:, None]
        self.w_true = np.empty((B, d))
        self.compute_time = np.ones((B, P))
        self.lr = np.empty(B)
        self.noise_std = np.empty(B)
        self.staleness = np.zeros(B, dtype=np.int64)
        self.beta = np.full(B, -1, dtype=np.int64)    # -1 = full view
        self.is_asp = np.zeros(B, dtype=bool)
        self.distributed = np.zeros(B, dtype=bool)
        # adaptive barrier-policy rows (dssp / ebsp / β-annealing): row
        # tags + per-row knobs; the static-policy fast path never reads
        # these (self.adaptive gates every use)
        self.is_dssp = np.zeros(B, dtype=bool)
        self.is_ebsp = np.zeros(B, dtype=bool)
        self.is_anneal = np.zeros(B, dtype=bool)
        self.pol_lo = np.zeros(B, dtype=np.int64)      # DSSP lower bound r
        self.beta_lo = np.zeros(B, dtype=np.int64)     # annealing β_min
        self.ebsp_range = np.zeros(B)                  # Elastic max_advance
        self.ebsp_alpha = np.full(B, 0.5)              # Elastic EMA α
        for b, cfg in enumerate(configs):
            rng = np.random.default_rng(cfg.seed)
            self.w_true[b], ct = draw_static_state(cfg, rng)
            self.compute_time[b, :cfg.n_nodes] = ct
            # default lr scales with the row's TRUE population, not the
            # padded batch width — grouping must not change results
            self.lr[b] = cfg.lr if cfg.lr is not None else 0.5 / cfg.n_nodes
            self.noise_std[b] = cfg.noise_std
            bar = cfg.barrier
            self.staleness[b] = bar.staleness
            self.is_asp[b] = isinstance(bar, ASP)
            if not self.is_asp[b] and bar.sample_size is not None:
                self.beta[b] = bar.sample_size
            self.distributed[b] = cfg.distributed_sampling
            kind = getattr(bar, "adaptive", "")
            if kind == "dssp":
                self.is_dssp[b] = True
                self.pol_lo[b] = bar.staleness_lo
            elif kind == "ebsp":
                self.is_ebsp[b] = True
                self.ebsp_range[b] = bar.max_advance
                self.ebsp_alpha[b] = bar.ema_alpha
            elif kind == "anneal":
                self.is_anneal[b] = True
                self.beta_lo[b] = bar.sample_size_lo
        self.full_view = (self.beta < 0) & ~self.is_asp
        self.sampled = self.beta >= 0
        self.adaptive = bool(self.is_dssp.any() or self.is_ebsp.any()
                             or self.is_anneal.any())
        #: per-row effective sample-slot cap (β clipped to the row's true
        #: peer count) — the annealing bounds live inside it
        self.beta_cap = np.maximum(np.minimum(self.beta, self.n_true - 1), 0)
        self.beta_lo = np.clip(self.beta_lo, 0, self.beta_cap)
        # ---- adaptive policy state (decisions read the OLD state; the
        # ---- end-of-tick update mirrors psp_tick_ref block 3b) ---------- #
        self.pol_thr = self.staleness.copy()           # DSSP threshold
        self.pol_ema = np.zeros((B, P))                # Elastic duration EMA
        self.pol_beta = np.where(self.is_anneal, self.beta_lo,
                                 np.maximum(self.beta, 0))
        self.w_true_norm = np.linalg.norm(self.w_true, axis=1)

        # one dynamics stream for the whole batch, seeded from all rows;
        # SFC64 because bulk draws are the engine's hottest path
        self.rng = np.random.Generator(np.random.SFC64(
            np.random.SeedSequence([int(c.seed) for c in configs]
                                   + [B, P, d])))

        # ---- dynamic state ---------------------------------------------- #
        self.w = np.zeros((B, d))
        self.pulled = np.zeros((B, P, d))
        self.steps = np.zeros((B, P), dtype=np.int64)
        self.alive = self.valid_slot.copy()
        self.computing = np.ones((B, P), dtype=bool)
        #: finish time while computing / next barrier-check time while not
        self.event_time = self.compute_time * (0.5 + self.rng.random((B, P)))
        #: continuous anchor of the node's current decision attempt
        self.ready = self.event_time.copy()
        self.blocked = np.zeros((B, P), dtype=bool)
        self.total_updates = np.zeros(B, dtype=np.int64)
        self.control_messages = np.zeros(B, dtype=np.int64)
        # per-draw control cost of the structured overlay (β lookups of
        # O(log N) hops + β step queries), matching OverlaySampler;
        # per-row because a ragged batch mixes populations
        self.hops_per_peer = np.maximum(
            1, np.ceil(np.log2(np.maximum(self.n_true, 2)))
        ).astype(np.int64) + 1

        # ---- tick grid + measurement grid ------------------------------- #
        ticks = np.arange(self.dt, self.duration + 1e-9, self.dt)
        if ticks.size == 0 or ticks[-1] < self.duration - 1e-9:
            ticks = np.append(ticks, self.duration)
        self.ticks = ticks
        self.m_times = np.arange(0.0, self.duration + 1e-9,
                                 self.measure_interval)
        self._trace_err: List[np.ndarray] = []
        self._trace_upd: List[np.ndarray] = []

        # ---- churn schedules: pre-sampled Poisson processes per row ----- #
        # i64[T, B] event counts per tick (tick i covers (t_{i-1}, t_i]);
        # empty rows for churn-free configs inside a churn batch
        if self.has_churn:
            edges = np.concatenate(([0.0], ticks))
            self.leave_counts = np.zeros((ticks.size, B), dtype=np.int64)
            self.join_counts = np.zeros((ticks.size, B), dtype=np.int64)
            for b, cfg in enumerate(configs):
                # sampled to the ROW's horizon: a merged shorter-duration
                # row must see no churn events past its own freeze point
                lt, jt = sample_churn_schedules(
                    self.rng, cfg.churn_leave_rate, cfg.churn_join_rate,
                    float(cfg.duration))
                self.leave_counts[:, b] = np.histogram(lt, bins=edges)[0]
                self.join_counts[:, b] = np.histogram(jt, bins=edges)[0]

    # ------------------------------------------------------------------ #
    def _measure(self) -> None:
        err = (np.linalg.norm(self.w - self.w_true, axis=1)
               / self.w_true_norm)
        self._trace_err.append(err)
        self._trace_upd.append(self.total_updates.copy())

    def _apply_updates(self, b_idx: np.ndarray, p_idx: np.ndarray) -> None:
        """Batched SGD pushes for every node that finished this tick.

        The residual is computed directly as X·(w_pulled − w*) − σ·ε, which
        folds the label draw into one projection; minibatch draws are f32
        (the simulation's noise floor is orders of magnitude above f32 eps).
        """
        K = b_idx.size
        X = self.rng.standard_normal((K, self.batch, self.d),
                                     dtype=np.float32)
        diff = (self.pulled[b_idx, p_idx]
                - self.w_true[b_idx]).astype(np.float32)
        eps = self.rng.standard_normal((K, self.batch), dtype=np.float32)
        resid = (np.einsum("kbd,kd->kb", X, diff)
                 - self.noise_std[b_idx, None].astype(np.float32) * eps)
        grads = np.einsum("kb,kbd->kd", resid, X) / self.batch
        # updates within a tick commute: each gradient depends only on the
        # node's pulled (stale) model, so the server sum is order-free.
        # b_idx comes from np.nonzero and is therefore sorted, so the
        # per-row sums are contiguous segments (reduceat ≫ np.add.at).
        rows, starts = np.unique(b_idx, return_index=True)
        self.w[rows] -= (self.lr[rows, None]
                         * np.add.reduceat(grads.astype(np.float64),
                                           starts, axis=0))
        self.total_updates += np.bincount(b_idx, minlength=self.B)

    def _sample_peers(self, bb: np.ndarray, pp: np.ndarray,
                      k: int) -> np.ndarray:
        """i64[K, k] peer indices: uniform without replacement, self excluded.

        For k ≪ P this is vectorized rejection sampling (draw k iid indices
        over the P−1 non-self slots, redraw rows with within-row collisions)
        — O(K·k) versus the O(K·P) of a full argpartition, which remains the
        fallback for dense samples.  No-churn path: every peer is alive.
        """
        K = bb.size
        if 3 * k >= self.P:
            scores = self.rng.random((K, self.P))
            scores[np.arange(K), pp] = 2.0
            return np.argpartition(scores, k - 1, axis=1)[:, :k]
        draw = self.rng.integers(0, self.P - 1, size=(K, k))
        draw += draw >= pp[:, None]          # skip over the self slot
        if k > 1:
            for _ in range(16):
                srt = np.sort(draw, axis=1)
                dup = (srt[:, 1:] == srt[:, :-1]).any(axis=1)
                if not dup.any():
                    break
                rows = np.flatnonzero(dup)
                redo = self.rng.integers(0, self.P - 1, size=(rows.size, k))
                redo += redo >= pp[rows, None]
                draw[rows] = redo
        return draw

    def _sample_peers_masked(self, bb: np.ndarray, pp: np.ndarray,
                             k: int) -> Tuple[np.ndarray, np.ndarray]:
        """Churn path: k alive-peer indices + validity, self/dead excluded.

        Masked argpartition over uniform scores; a slot is valid iff its
        score stayed below the dead/self sentinel, which caps the effective
        sample at the row's alive-peer count — exactly the event engine's
        ``beta = min(beta, len(pool))`` under a compressed alive pool.
        """
        K = bb.size
        scores = self.rng.random((K, self.P))
        scores[~self.alive[bb]] = 2.0
        scores[np.arange(K), pp] = 2.0
        take = np.argpartition(scores, min(k, self.P - 1), axis=1)[:, :k]
        valid = np.take_along_axis(scores, take, axis=1) < 1.5
        return take, valid

    def _barrier_pass(self, cand: np.ndarray) -> np.ndarray:
        """Masked barrier predicates; bool[B, P], valid where ``cand``."""
        passed = np.zeros((self.B, self.P), dtype=bool)
        passed[self.is_asp] = True
        if self.full_view.any():
            fv = self.full_view
            fv_steps = self.steps[fv]
            # min over *alive* steps: a departed straggler's frozen counter
            # must not gate waiters (the event engine's churn-wake fix)
            masked = np.where(self.alive[fv], fv_steps,
                              np.iinfo(np.int64).max)
            lag = fv_steps - masked.min(axis=1, keepdims=True)
            thr = np.broadcast_to(self.staleness[fv, None], fv_steps.shape)
            if self.adaptive:
                # adaptive rows swap their effective threshold in: DSSP
                # the carried dynamic bound, Elastic-BSP the per-node
                # EMA step credit (same formulas as psp_tick_ref /
                # barrier_kernel.elastic_slack)
                thr = np.where(self.is_dssp[fv, None],
                               self.pol_thr[fv, None], thr)
                if self.is_ebsp.any():
                    live = np.where(self.alive, self.pol_ema, 0.0)
                    frac = 1.0 - self.pol_ema / np.maximum(
                        live.max(axis=1, keepdims=True), 1e-9)
                    slack = np.floor(self.ebsp_range[:, None] * frac
                                     ).astype(np.int64)
                    thr = np.where(self.is_ebsp[fv, None], slack[fv], thr)
            passed[fv] = lag <= thr
        sm = cand & self.sampled[:, None]
        b_idx, p_idx = np.nonzero(sm)
        if b_idx.size:
            betas = self.beta[b_idx]
            if self.adaptive:
                # β-annealing rows sample with their carried β
                betas = np.where(self.is_anneal[b_idx],
                                 self.pol_beta[b_idx], betas)
            for beta in np.unique(betas):
                pick = betas == beta
                bb, pp = b_idx[pick], p_idx[pick]
                k = min(int(beta), self.P - 1)
                if k <= 0:
                    passed[bb, pp] = True   # S = ∅ degenerates to ASP
                    continue
                if self.has_churn:
                    take, valid = self._sample_peers_masked(bb, pp, k)
                    n_sampled = valid.sum(axis=1)
                else:
                    take = self._sample_peers(bb, pp, k)
                    valid = np.ones_like(take, dtype=bool)
                    n_sampled = np.full(bb.size, k)
                peer_steps = self.steps[bb[:, None], take]
                my = self.steps[bb, pp]
                passed[bb, pp] = np.all(
                    (my[:, None] - peer_steps
                     <= self.staleness[bb][:, None]) | ~valid, axis=1)
                dist = self.distributed[bb]
                if dist.any():
                    self.control_messages += (
                        self.hops_per_peer
                        * np.bincount(bb[dist], weights=n_sampled[dist],
                                      minlength=self.B).astype(np.int64))
        return passed

    # ------------------------------------------------------------------ #
    # churn: batched leave/join event processing
    # ------------------------------------------------------------------ #
    def _churn_leave(self, rows: np.ndarray) -> None:
        """One leave event in each flagged row: kill a random alive node.

        Fires only while more than two nodes are alive (the population can
        drop to two), as the event engine; the event is consumed either
        way (a too-small row just skips the effect).
        """
        rows = rows & (self.alive.sum(axis=1) > 2)
        b = np.flatnonzero(rows)
        if b.size == 0:
            return
        scores = self.rng.random((b.size, self.P))
        scores[~self.alive[b]] = -1.0
        victim = scores.argmax(axis=1)
        self.alive[b, victim] = False

    def _churn_join(self, rows: np.ndarray, t: float) -> None:
        """One join event per flagged row: revive a random dead node.

        The joiner restarts at the current max alive step (the event
        engine's fresh-start rule) and decides this tick.
        """
        rows = rows & ~self.alive.all(axis=1)
        b = np.flatnonzero(rows)
        if b.size == 0:
            return
        scores = self.rng.random((b.size, self.P))
        scores[self.alive[b]] = -1.0
        node = scores.argmax(axis=1)
        self.alive[b, node] = True
        fresh = np.where(self.alive[b], self.steps[b],
                         np.iinfo(np.int64).min).max(axis=1)
        self.steps[b, node] = fresh
        self.computing[b, node] = False
        self.event_time[b, node] = t
        self.ready[b, node] = t
        self.blocked[b, node] = False

    def _process_churn(self, t: float, leave_n: np.ndarray,
                       join_n: np.ndarray) -> None:
        """Fire this tick's pre-sampled leave/join events, batched per round
        (several events per row per tick are possible but rare)."""
        leave_n, join_n = leave_n.copy(), join_n.copy()
        while (leave_n > 0).any() or (join_n > 0).any():
            self._churn_leave(leave_n > 0)
            self._churn_join(join_n > 0, t)
            leave_n -= leave_n > 0
            join_n -= join_n > 0

    # ------------------------------------------------------------------ #
    def _tick(self, t: float, tick_index: int) -> None:
        """Advance the whole batch by one grid tick (phases 0–3)."""
        if self.has_churn:
            self._process_churn(t, self.leave_counts[tick_index],
                                self.join_counts[tick_index])

        # 1. finishes: push updates, advance steps, become "deciding"
        fin = self.computing & self.alive & (self.event_time <= t + _EPS)
        # latest finish per row this tick: a full-view waiter unblocked
        # this tick was gated by (at most) that finish, so anchoring
        # there instead of the tick boundary removes the systematic
        # dt/2-per-round quantisation loss for BSP/SSP
        row_unblock = np.full(self.B, t)
        if fin.any():
            b_idx, p_idx = np.nonzero(fin)
            rows, starts = np.unique(b_idx, return_index=True)
            row_last = np.maximum.reduceat(self.event_time[fin], starts)
            row_unblock[rows] = np.minimum(row_last, t)
            self._apply_updates(b_idx, p_idx)
            self.steps[fin] += 1
            self.computing[fin] = False
            self.ready[fin] = self.event_time[fin]  # true finish time
            self.blocked[fin] = False

        # 2. barrier decisions for every due deciding node
        cand = ~self.computing & self.alive & (self.event_time <= t + _EPS)
        if cand.any():
            passed = self._barrier_pass(cand)
            start = cand & passed
            if start.any():
                b_idx, p_idx = np.nonzero(start)
                # anchor at the continuous ready time; a full-view node
                # unblocked by a peer's finish starts at that finish
                # (the grid analogue of the event simulator's
                # min-moved wakeup)
                t0 = np.where(self.blocked[start]
                              & self.full_view[b_idx],
                              np.maximum(row_unblock[b_idx],
                                         self.ready[start]),
                              self.ready[start])
                self.pulled[b_idx, p_idx] = self.w[b_idx]
                dur = (self.compute_time[b_idx, p_idx]
                       * (0.5 + self.rng.random(b_idx.size)))
                self.event_time[start] = t0 + dur
                self.computing[start] = True
                self.blocked[start] = False
                if self.adaptive and self.is_ebsp.any():
                    # Elastic-BSP folds each starter's freshly drawn
                    # duration into its per-node EMA (the grid engines'
                    # observation point — see psp_tick_ref block 3b)
                    eb = self.is_ebsp[b_idx]
                    if eb.any():
                        al = self.ebsp_alpha[b_idx[eb]]
                        old = self.pol_ema[b_idx[eb], p_idx[eb]]
                        self.pol_ema[b_idx[eb], p_idx[eb]] = \
                            (1.0 - al) * old + al * dur[eb]
            fail = cand & ~passed
            if fail.any():
                self.blocked[fail] = True
                # sampled rows re-poll on the poll cadence; full-view
                # rows stay due and re-check next tick
                sm_fail = fail & self.sampled[:, None]
                self.ready[sm_fail] += self.poll_interval
                self.event_time[sm_fail] = self.ready[sm_fail]

        # 2b. adaptive-policy state updates from this tick's observed
        #     post-finish step spread (decisions above used the OLD state)
        if self.adaptive:
            masked = np.where(self.alive, self.steps,
                              np.iinfo(np.int64).min)
            gap = masked.max(axis=1) - np.where(
                self.alive, self.steps, np.iinfo(np.int64).max).min(axis=1)
            gap = np.where(self.alive.any(axis=1), gap, 0)
            self.pol_thr = np.where(
                self.is_dssp,
                np.clip(gap, self.pol_lo, self.staleness), self.pol_thr)
            self.pol_beta = np.where(
                self.is_anneal,
                np.clip(self.beta_lo + gap - self.staleness,
                        self.beta_lo, self.beta_cap), self.pol_beta)

    def _results(self, errs: np.ndarray, upds: np.ndarray) -> List[SimResult]:
        """Assemble per-row :class:`SimResult`\\ s from [B, M] traces.

        A merged batch can carry rows with different horizons (jax
        backend): each row's traces are cut at its own duration — the
        trailing grid points belong to longer-lived batch mates.
        """
        final_err = (np.linalg.norm(self.w - self.w_true, axis=1)
                     / self.w_true_norm)
        out = []
        for b in range(self.B):
            n = int(self.n_true[b])   # drop ragged padding slots
            mb = min(errs.shape[1],
                     int(np.searchsorted(self.m_times,
                                         self.row_duration[b] + 1e-9)))
            out.append(SimResult(
                steps=self.steps[b, :n].copy(),
                times=self.m_times[:mb].copy(),
                errors=errs[b, :mb].copy(),
                server_updates=upds[b, :mb].copy(),
                control_messages=int(self.control_messages[b]),
                total_updates=int(self.total_updates[b]),
                mean_progress=float(self.steps[b][self.alive[b]].mean()),
                final_error=float(final_err[b]),
            ))
        return out

    def run(self) -> List[SimResult]:
        """Advance the batch over the whole tick grid on this backend."""
        if self.backend == "jax":
            from repro.core import vector_sim_jax
            return vector_sim_jax.run_batch(self)

        self._measure()                      # t = 0 trace point
        m_next = 1
        for i, t in enumerate(self.ticks):
            self._tick(t, i)
            # 3. error / server-update traces on the measurement grid
            while m_next < self.m_times.size and \
                    self.m_times[m_next] <= t + _EPS:
                self._measure()
                m_next += 1

        errs = np.stack(self._trace_err, axis=1)        # [B, M]
        upds = np.stack(self._trace_upd, axis=1)        # [B, M]
        return self._results(errs, upds)


# --------------------------------------------------------------------------- #
def run_sweep(configs: Sequence[SimConfig], *,
              dt: Optional[float] = None,
              backend: str = "numpy") -> List[SimResult]:
    """Run a batch of simulations on the vectorized grid engine.

    Configs are grouped by structural shape and each group is advanced as
    one :class:`VectorSimulator` — churn configs run natively with
    per-row alive masks; nothing falls back to the event-driven reference.
    The numpy backend groups strictly (identical ``n_nodes``, duration
    and churn-ness per batch); the jax backend groups by the relaxed
    :func:`_merge_key`, padding ragged ``n_nodes`` with permanently-dead
    alive-mask slots and freezing shorter-duration rows at their own
    horizon, so mixed sweeps run as one chunk-scan schedule per
    (dim, batch, cadence) shape.  Results come back in input order
    regardless of backend or grouping.

    Args:
      configs: scenario list (any mix of shapes/barriers/churn).
      dt: grid width; defaults to each group's ``poll_interval``.
      backend: ``"numpy"`` (array ops per tick) or ``"jax"`` (donated
        chunked scans over the tick grid with the fused tick of
        :mod:`repro.kernels.psp_tick`, sharded over the device mesh —
        :mod:`repro.core.vector_sim_jax`).

    On the jax backend each group's static-state draw (the
    :class:`VectorSimulator` constructor) is the profiler span
    ``sweep.init``; :func:`repro.core.vector_sim_jax.run_batch` spans the
    rest.
    """
    results: List[Optional[SimResult]] = [None] * len(configs)
    key_fn = _merge_key if backend == "jax" else _group_key
    groups: Dict[Tuple, List[int]] = {}
    for i, cfg in enumerate(configs):
        groups.setdefault(key_fn(cfg), []).append(i)
    for idx in groups.values():
        with _init_span(backend):
            sim = VectorSimulator([configs[i] for i in idx], dt=dt,
                                  backend=backend)
        for i, res in zip(idx, sim.run()):
            results[i] = res
    return results  # type: ignore[return-value]


def _init_span(backend: str):
    """The profiler span ``sweep.init`` (``jax.profiler.TraceAnnotation``)
    of a jax-backend sweep; the numpy backend does not import jax."""
    if backend != "jax":
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation("sweep.init")
