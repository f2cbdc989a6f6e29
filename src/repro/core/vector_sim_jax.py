"""JAX backend of the vectorized sweep engine: donated, sharded chunk scans.

One grid tick — control plane (churn, finish bookkeeping, barrier
decisions, start/re-poll anchoring) *and* data plane (masked SGD push +
model-view pull) — is one fused kernel: the Pallas tick
(:mod:`repro.kernels.psp_tick`) on TPU, its pure-jnp twin on CPU,
selected by :func:`repro.kernels.ops.psp_tick` with ``impl="auto"``
(override with the ``PSP_TICK_IMPL`` env var, e.g. ``interpret`` to
exercise the kernel on CPU).

:func:`run_batch` executes the tick grid as a sequence of **chunked,
donated scans** laid out by :func:`repro.core.sweep_plan.plan_sweep`:

* The grid is blocked into *superticks* of ``stride`` ticks.  Each
  supertick draws its whole noise block in a handful of batched
  ``jax.random`` calls, runs an inner ``lax.scan`` over its ticks, and
  emits **one** trace record — traces are only consumed on the
  measurement grid, so recording every tick is pure waste.
* Superticks are grouped into pow2-length chunks, each a separate call
  into one jitted scan whose ``(B, P)`` carry is **donated** — XLA
  reuses the state pytree's buffers across chunks instead of
  double-buffering them.  The chunk loop early-exits once every row is
  past its horizon, so scheduled-but-dead superticks are never executed.
* The batch is sharded over a 2-D ``(rows, nodes)`` device mesh with
  ``shard_map`` (axis names from :mod:`repro.parallel.sharding`; the
  degenerate 1×1 mesh on an unflagged CPU IS the single-device engine).
  The scenario dimension shards over ``rows``; the P node slots — state,
  node-keyed draws, the minibatch blob — stay **node-sliced** over
  ``nodes`` end-to-end, and the tick's cross-node reductions run as
  collectives (:func:`repro.kernels.psp_tick.psp_tick_sharded`).
  Per-row noise is keyed by *global row id* and node-keyed noise by
  *global node id*, with every draw either sliced from the full-width
  stream or assembled from disjoint global-id blocks, so every mesh
  factorization consumes identical draws and ``run_sweep(backend="jax")``
  is **bit-identical** across device counts *and* factorizations —
  multi-device is transparent (``tests/test_vector_sim_jax.py``'s
  cross-mesh equivalence suite pins this).

The scan itself performs zero host transfers: inputs are staged (and
sharded) once by :func:`_prepare`, chunks hand the donated carry to each
other on device, and exactly one ``device_get`` at the end fetches
traces plus final state (``tests/test_vector_sim_jax.py`` holds
``transfer_guard`` and donation tests on this).

Semantics mirror :class:`repro.core.vector_sim.VectorSimulator`'s numpy
tick exactly (same phases, same anchoring, same alive-mask churn rules);
only the dynamics RNG differs (threefry vs SFC64), so the two backends
agree at the distribution level and each is individually deterministic
(golden traces in ``tests/test_vector_sim_jax.py``).

Design notes for the hot path:

* Barrier predicates and the straggler duration model are single-sourced
  in :mod:`repro.core.barrier_kernel` — the same code the SPMD trainer
  (:mod:`repro.core.spmd_psp`) routes through — and β-samples come from
  the shared :mod:`repro.core.sampling` primitives.  All noise is drawn
  outside the kernel, so every ``impl`` consumes an identical RNG stream.
* Without churn, one peer-score draw per tick is shared across the B
  scenario rows (each row's marginal stays an exact uniform β-sample);
  likewise one minibatch draw per (tick, node) is shared across rows.
  Cross-row correlation is irrelevant for per-row statistics — use the
  numpy backend when cross-row independence matters (it decorrelates via
  finisher-ordered stream consumption).
* Ragged batches: scenario groups that differ in ``n_nodes``, churn-ness
  or **duration** are padded to a common P and merged into one schedule —
  padded node slots are permanently dead ``alive``-mask entries, and a
  row past its own horizon freezes (the fused tick's ``active`` gate), so
  ragged sweeps cost one compile instead of one per shape.
* Times are f32 (no global x64 flag); the due-comparison epsilon scales
  with ``dt`` to stay above f32 resolution at the horizon.
* The compiled chunk scan is cached by structural signature
  (``P, d, batch, k_max, has_churn, masked, adaptive, impl, stride,
  rows, nodes``) so repeated sweeps of the same shape (the common
  benchmark/test pattern) compile once per chunk length.
* Node sharding (``nodes > 1``, opt-in via ``PSP_SWEEP_MESH=RxN``) keeps
  the carried ``(B, P)`` state and the supertick noise blocks sliced to
  ``P_loc = P / nodes`` per shard — the memory that caps system size at
  100k+ nodes — while the tick gathers only one tick's worth of
  transients to full width where bit-identity demands reference shapes
  (the β-sample peer view, the data-plane contraction; see
  ``psp_tick_sharded``).  The nodes axis must divide P exactly — the
  planner clamps to the nearest divisor — because a padded node slot
  would widen the full-view reductions and flip fully-alive batches onto
  the masked sampling path.
* Adaptive barrier policies (dssp / ebsp / β-annealing) ride in the
  scanned carry as the :data:`~repro.kernels.psp_tick.POLICY_STATE_KEYS`
  pytree entries; static batches have ``adaptive=False`` and compile the
  exact pre-policy tick (the keys are simply absent), which is what
  keeps the static golden traces bit-identical.  Adaptive rows draw no
  extra noise — the annealed β consumes the same pre-drawn score slots
  (``k_max`` covers β_max) — so the planner's noise budget is unchanged.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import env
from repro.core.simulator import SimResult
from repro.core.sweep_plan import plan_sweep
from repro.kernels import ops
from repro.kernels.psp_tick import POLICY_STATE_KEYS, STATE_KEYS
from repro.parallel.sharding import (SWEEP_NODES_AXIS, SWEEP_ROWS_AXIS,
                                     sweep_mesh)

__all__ = ["run_batch", "tick_impl"]

#: params entries replicated across the mesh (everything else is per-row
#: or per-node and therefore sharded)
_REPLICATED_PARAMS = frozenset({"key", "eps", "poll"})

#: params entries with a trailing node dimension — sharded over the
#: ``nodes`` mesh axis alongside the node-dimensioned carry
_NODE_PARAMS = frozenset({"compute_time", "valid_slot"})

#: carry entries with a node dimension (axis 1); everything else in the
#: carry is per-row only and rides replicated over the nodes axis
_NODE_CARRY = frozenset({"steps", "alive", "computing", "event_time",
                         "ready", "blocked", "pulled", "pol_ema"})


def tick_impl() -> str:
    """Tick implementation (``PSP_TICK_IMPL`` env override).

    ``auto`` (default): Pallas kernel on TPU, jnp reference elsewhere;
    ``pallas`` / ``interpret`` / ``ref`` force a path (``interpret`` runs
    the kernel through the Pallas interpreter — the CPU test/bench path).
    """
    return env.get_str("PSP_TICK_IMPL")


def _row_spec(ndim: int) -> PartitionSpec:
    """Leading-axis row sharding for an ``ndim``-rank per-row array."""
    return PartitionSpec(*((SWEEP_ROWS_AXIS,) + (None,) * (ndim - 1)))


def _node_spec(ndim: int) -> PartitionSpec:
    """(B, P, ...) sharding: rows on axis 0, node slots on axis 1."""
    return PartitionSpec(*((SWEEP_ROWS_AXIS, SWEEP_NODES_AXIS)
                           + (None,) * (ndim - 2)))


def _specs(params: Dict, carry: Dict, xs: Dict) -> Tuple[Dict, Dict, Dict]:
    """(params, carry, xs) partition-spec pytrees for the chunk scan.

    Per-row arrays shard on their leading (B) axis over ``rows``;
    node-dimensioned arrays (:data:`_NODE_CARRY` / :data:`_NODE_PARAMS`)
    additionally shard their P axis over ``nodes``; ``node_ids`` shards
    its single axis over both (nodes-major: each node column's draw-id
    block splits over the rows axis — see the supertick blob draw); the
    churn schedules shard on their trailing row axis; everything else is
    replicated.  The same trees drive both ``shard_map`` and the input
    staging in :func:`_prepare`, so staged buffers land exactly where the
    compiled scan expects them (no resharding copy on call).
    """
    def p_spec(k, v):
        if k in _REPLICATED_PARAMS:
            return PartitionSpec()
        if k == "node_ids":
            return PartitionSpec((SWEEP_NODES_AXIS, SWEEP_ROWS_AXIS))
        if k in _NODE_PARAMS:
            return _node_spec(np.ndim(v))
        return _row_spec(np.ndim(v))

    p_specs = {k: p_spec(k, v) for k, v in params.items()}
    c_specs = {k: (_node_spec(np.ndim(v)) if k in _NODE_CARRY
                   else _row_spec(np.ndim(v))) for k, v in carry.items()}
    x_specs = {"sup": PartitionSpec(), "t": PartitionSpec(),
               "leave": PartitionSpec(None, None, SWEEP_ROWS_AXIS),
               "join": PartitionSpec(None, None, SWEEP_ROWS_AXIS)}
    return p_specs, c_specs, {k: x_specs[k] for k in xs}


@functools.lru_cache(maxsize=32)
def _compiled_chunk(P: int, d: int, batch: int, k_max: int, has_churn: bool,
                    masked: bool, adaptive: bool, impl: str, stride: int,
                    rows: int, nodes: int):
    """(jitted chunk scan, mesh), specialised on structural shape.

    The returned function maps ``(params, carry, xs) -> (carry', (err,
    upd))`` where ``xs`` covers one chunk of superticks; the carry is
    donated, the B axis is sharded over the ``rows`` mesh axis and the P
    node slots over ``nodes``.  Chunk length only changes input shapes,
    so jit's own cache specialises per pow2 block while this wrapper
    caches the mesh + shard_map plumbing.
    """
    mesh = sweep_mesh(rows, nodes)
    p_loc = P // nodes           # planner guarantees nodes | P
    node_axis = SWEEP_NODES_AXIS if nodes > 1 else None
    kw = dict(k_max=k_max, has_churn=has_churn, masked=masked,
              adaptive=adaptive, impl=impl, node_axis=node_axis)
    state_keys = STATE_KEYS + (POLICY_STATE_KEYS if adaptive else ())

    def tick(params, carry, xt):
        state = {k: carry[k] for k in state_keys}
        rand = {k: xt[k] for k in xt
                if k in ("dur", "scores", "u1", "leave", "join", "X", "mb")}
        state, out = ops.psp_tick(state, rand, params, xt["t"],
                                  xt["lc"], xt["jc"], **kw)
        return {**state,
                "total_updates": carry["total_updates"] + out["n_fin"],
                "control": carry["control"] + out["ctrl"]}, None

    def supertick(params, carry, x):
        # one batched noise block per supertick: a handful of keyed
        # jax.random calls instead of per-tick dispatch.  Per-row noise
        # is keyed by global row id, node-keyed noise by global node id,
        # and every draw reaches the tick either as the shard's slice of
        # the full-width stream (row-keyed draws are drawn full and
        # sliced to the local node columns — the values cannot depend on
        # the factorization) or assembled from disjoint global-id blocks
        # (the blob and shared-score draws below), so every mesh shape
        # consumes identical noise — the cross-mesh bit-identity
        # invariant.  On the 1×1 mesh all slices are identity and this
        # is exactly the single-device draw.  The draws run under the
        # device scope ``sweep.noise``.
        with jax.named_scope("sweep.noise"):
            row_ids, node_ids = params["row_ids"], params["node_ids"]
            nid0 = lax.axis_index(SWEEP_NODES_AXIS) * p_loc
            k_sup = jax.random.fold_in(params["key"], x["sup"])
            k_mini, k_samp, k_dur, k_churn = jax.random.split(k_sup, 4)
            fold = jax.vmap(jax.random.fold_in, (None, 0))
            # minibatch blob keyed per (tick, node): the draw comes out in
            # scan layout directly (stride leading), so no supertick-sized
            # transpose sits between the RNG and the tick loop.  node_ids is
            # nodes-major — each node column's rows-padded id block splits
            # over the rows axis, so the gather over *rows* reassembles the
            # column's global ids [nid0, nid0 + p_loc) in order and the blob
            # stays node-sliced (the 100k-node memory win): no shard ever
            # materialises the (stride, P, m, d+1) block
            kt = fold(k_mini, x["sup"] * stride + jnp.arange(stride))
            blob_loc = jax.vmap(lambda k: jax.vmap(
                lambda kk: jax.random.normal(kk, (batch, d + 1)))(
                    fold(k, node_ids)))(kt)           # (stride, ids_loc, ...)
            # (stride, p_loc, ...)
            blob = lax.all_gather(blob_loc, SWEEP_ROWS_AXIS, axis=1,
                                  tiled=True)[:, :p_loc]
            dur = jnp.moveaxis(jax.vmap(
                lambda k: jax.random.uniform(k, (stride, P)))(
                    fold(k_dur, row_ids)), 1, 0)          # (stride, b_loc, P)
            xt = {"t": x["t"], "lc": x["leave"], "jc": x["join"],
                  "X": blob[..., :d], "mb": blob[..., d],
                  "dur": lax.dynamic_slice_in_dim(dur, nid0, p_loc, 2)}
            if k_max > 0:
                if masked:
                    sc = jnp.moveaxis(jax.vmap(
                        lambda k: jax.random.uniform(k, (stride, P, P)))(
                            fold(k_samp, row_ids)), 1, 0)
                    # slice the deciding-node axis; peers keep full width
                    xt["scores"] = lax.dynamic_slice_in_dim(sc, nid0, p_loc, 2)
                elif k_max == 1:
                    u1 = jax.random.uniform(k_samp, (stride, P))
                    xt["u1"] = lax.dynamic_slice_in_dim(u1, nid0, p_loc, 1)
                else:
                    sc_loc = jax.vmap(
                        lambda k: jax.random.uniform(k, (stride, P)))(
                            fold(k_samp, node_ids))
                    sc = lax.all_gather(sc_loc, SWEEP_ROWS_AXIS, tiled=True)
                    xt["scores"] = jnp.moveaxis(sc, 1, 0)[:, :p_loc]
            if has_churn:
                cu = jax.vmap(
                    lambda k: jax.random.uniform(k, (stride, 2, P)))(
                        fold(k_churn, row_ids))
                xt["leave"] = lax.dynamic_slice_in_dim(
                    jnp.moveaxis(cu[:, :, 0], 0, 1), nid0, p_loc, 2)
                xt["join"] = lax.dynamic_slice_in_dim(
                    jnp.moveaxis(cu[:, :, 1], 0, 1), nid0, p_loc, 2)
        carry, _ = lax.scan(functools.partial(tick, params), carry, xt)
        err = (jnp.linalg.norm(carry["w"] - params["w_true"], axis=1)
               / params["w_true_norm"])
        return carry, (err, carry["total_updates"])

    def chunk(params, carry, xs):
        return lax.scan(functools.partial(supertick, params), carry, xs)

    def sharded(params, carry, xs):
        specs = _specs(params, carry, xs)
        # check_vma=False: pallas_call (the interpret/TPU tick) has no
        # varying-axes rule; correctness is pinned by the cross-mesh
        # bit-identity suite instead.  Traces (and the per-row carry) are
        # replicated over the nodes axis — their out_specs mention only
        # rows, so shard_map keeps one copy
        return jax.shard_map(chunk, mesh=mesh, in_specs=specs,
                             out_specs=(specs[1],
                                        (PartitionSpec(None, SWEEP_ROWS_AXIS),
                                         PartitionSpec(None,
                                                       SWEEP_ROWS_AXIS))),
                             check_vma=False)(params, carry, xs)

    return jax.jit(sharded, donate_argnums=(1,)), mesh


def _measure_idx(sim) -> np.ndarray:
    """Global tick index of each measurement point (t > 0).

    Single definition on purpose: the planner aligns the record stride on
    these indices and :func:`run_batch` maps them onto supertick records
    with ``(m_idx + 1) // stride − 1`` — both sides must see the exact
    same epsilon and slicing or traces silently shift by a record.
    """
    return np.searchsorted(sim.ticks, sim.m_times[1:] - 1e-9)


def _prepare(sim):
    """Stage a batch: (chunk fn, plan, params, carry, xs chunks) on device.

    Everything the grid loop touches is materialised and sharded here, so
    the chunk loop itself performs zero host transfers; the zero-copy
    test in ``tests/test_vector_sim_jax.py`` runs this staging, then
    executes the chunks under ``jax.transfer_guard("disallow")``.
    """
    B, P, d = sim.B, sim.P, sim.d
    f32 = jnp.float32
    k_max = int(min(max(int(sim.beta.max(initial=-1)), 0), P - 1))
    masked = sim.has_churn or bool((sim.n_true < P).any())
    eps = max(1e-9, 1e-3 * sim.dt)   # above f32 resolution at the horizon
    T = sim.ticks.size
    plan = plan_sweep(T, _measure_idx(sim), B, P, batch=sim.batch, d=d,
                      k_max=k_max,
                      masked=masked, has_churn=sim.has_churn)
    Bp = plan.b_pad

    def pad_rows(a, fill=0):
        if Bp == B:
            return a
        pad = np.full((Bp - B,) + a.shape[1:], fill, dtype=a.dtype)
        return np.concatenate([a, pad], axis=0)

    seed = np.random.SeedSequence(
        [int(c.seed) for c in sim.configs] + [B, P, d]).generate_state(1)[0]
    # node-keyed draw ids, nodes-major: each node column's global ids
    # [n·p_loc, (n+1)·p_loc) padded up to the rows axis (the pad ids
    # overlap the next column — drawn redundantly, sliced away after the
    # rows gather).  On the 1-D mesh this is exactly arange(node_pad).
    col = plan.node_pad // plan.nodes
    node_ids = (np.arange(col)[None, :]
                + plan.p_loc * np.arange(plan.nodes)[:, None]).reshape(-1)
    params = {
        "key": jax.random.PRNGKey(int(seed)),
        "eps": jnp.asarray(eps, f32),
        "poll": jnp.asarray(sim.poll_interval, f32),
        "row_ids": jnp.arange(Bp, dtype=jnp.int32),
        "node_ids": jnp.asarray(node_ids, jnp.int32),
        "w_true": jnp.asarray(pad_rows(sim.w_true), f32),
        # padded rows never tick; a unit norm keeps their (discarded)
        # error trace finite
        "w_true_norm": jnp.asarray(pad_rows(sim.w_true_norm, 1.0), f32),
        "compute_time": jnp.asarray(pad_rows(sim.compute_time, 1.0), f32),
        "lr": jnp.asarray(pad_rows(sim.lr), f32),
        "noise_std": jnp.asarray(pad_rows(sim.noise_std), f32),
        "horizon": jnp.asarray(pad_rows(sim.row_duration, -1.0), f32),
        "staleness": jnp.asarray(pad_rows(sim.staleness), jnp.int32),
        "beta_clip": jnp.asarray(
            pad_rows(np.clip(sim.beta, 0, sim.n_true - 1)), jnp.int32),
        "is_asp": jnp.asarray(pad_rows(sim.is_asp)),
        "full_view": jnp.asarray(pad_rows(sim.full_view)),
        "sampled": jnp.asarray(pad_rows(sim.sampled)),
        "valid_slot": jnp.asarray(pad_rows(sim.valid_slot)),
        "dist_hops": jnp.asarray(
            pad_rows(np.where(sim.distributed & sim.sampled,
                              sim.hops_per_peer, 0)), jnp.int32),
    }
    adaptive = bool(getattr(sim, "adaptive", False))
    if adaptive:
        # adaptive-policy row tags + knobs; padded rows are tagged static
        # (they are frozen anyway)
        params.update(
            is_dssp=jnp.asarray(pad_rows(sim.is_dssp)),
            is_ebsp=jnp.asarray(pad_rows(sim.is_ebsp)),
            is_anneal=jnp.asarray(pad_rows(sim.is_anneal)),
            pol_lo=jnp.asarray(pad_rows(sim.pol_lo), jnp.int32),
            beta_lo=jnp.asarray(pad_rows(sim.beta_lo), jnp.int32),
            ebsp_range=jnp.asarray(pad_rows(sim.ebsp_range), f32),
            ebsp_alpha=jnp.asarray(pad_rows(sim.ebsp_alpha), f32),
        )
    carry = {
        "w": jnp.zeros((Bp, d), f32),
        "pulled": jnp.zeros((Bp, P, d), f32),
        "steps": jnp.zeros((Bp, P), jnp.int32),
        "alive": jnp.asarray(pad_rows(sim.alive)),
        "computing": jnp.asarray(pad_rows(sim.computing)),
        "event_time": jnp.asarray(pad_rows(sim.event_time.astype(
            np.float32), 1.0)),
        "ready": jnp.asarray(pad_rows(sim.ready.astype(np.float32), 1.0)),
        "blocked": jnp.asarray(pad_rows(sim.blocked)),
        "total_updates": jnp.zeros(Bp, jnp.int32),
        "control": jnp.zeros(Bp, jnp.int32),
        "pend_leave": jnp.zeros(Bp, jnp.int32),
        "pend_join": jnp.zeros(Bp, jnp.int32),
    }
    if adaptive:
        # policy state joins the scanned carry (donated with the rest)
        carry.update(
            pol_thr=jnp.asarray(pad_rows(sim.pol_thr), jnp.int32),
            pol_ema=jnp.asarray(pad_rows(sim.pol_ema.astype(np.float32))),
            pol_beta=jnp.asarray(pad_rows(sim.pol_beta), jnp.int32),
        )

    # scheduled tick grid: live ticks, then dead padding beyond every
    # horizon (the fused tick's active gate makes them no-ops)
    T_sched = plan.n_ticks
    dt = float(sim.dt)
    t_sched = np.concatenate(
        [sim.ticks, sim.ticks[-1] + dt * np.arange(1, T_sched - T + 1)]
    ).astype(np.float32)
    lc = np.zeros((T_sched, Bp), np.int32)
    jc = np.zeros((T_sched, Bp), np.int32)
    if sim.has_churn:
        lc[:T, :B] = sim.leave_counts
        jc[:T, :B] = sim.join_counts

    chunk_fn, mesh = _compiled_chunk(P, d, sim.batch, k_max, sim.has_churn,
                                     masked, adaptive, tick_impl(),
                                     plan.stride, plan.rows, plan.nodes)
    p_specs, c_specs, _ = _specs(params, carry,
                                 {"sup": 0, "t": 0, "leave": 0, "join": 0})
    shard = lambda spec: NamedSharding(mesh, spec)
    params = jax.device_put(params,
                            {k: shard(s) for k, s in p_specs.items()})
    carry = jax.device_put(carry, {k: shard(s) for k, s in c_specs.items()})

    xs_chunks = []
    rec = 0
    for n_rec in plan.chunks:
        lo, hi = rec * plan.stride, (rec + n_rec) * plan.stride
        xs = {
            "sup": jnp.arange(rec, rec + n_rec, dtype=jnp.int32),
            "t": jnp.asarray(
                t_sched[lo:hi].reshape(n_rec, plan.stride)),
            "leave": jnp.asarray(
                lc[lo:hi].reshape(n_rec, plan.stride, Bp)),
            "join": jnp.asarray(
                jc[lo:hi].reshape(n_rec, plan.stride, Bp)),
        }
        _, _, x_specs = _specs(params, carry, xs)
        xs_chunks.append(jax.device_put(
            xs, {k: shard(s) for k, s in x_specs.items()}))
        rec += n_rec
    return chunk_fn, plan, params, carry, xs_chunks


def run_batch(sim) -> List[SimResult]:
    """Run a :class:`~repro.core.vector_sim.VectorSimulator` batch on jax.

    Consumes the simulator's numpy-initialised static state (identical to
    the numpy backend: per-seed init replay, initial busy clocks, churn
    schedules), executes the planned chunk scans with the fused tick —
    donated carry, sharded rows, one trace record per supertick — and
    writes the final state back so result assembly is shared with the
    numpy path.  One ``device_get`` per sweep moves the traces and final
    state to the host together.

    Its host phases are profiler spans (``jax.profiler.TraceAnnotation``)
    on the device trace's clock: ``sweep.stage`` (:func:`_prepare`),
    ``sweep.dispatch`` (the chunk loop), ``sweep.fetch`` (the wait and the
    ``device_get``) and ``sweep.results`` (traces, write-back, results).
    """
    with jax.profiler.TraceAnnotation("sweep.stage"):
        chunk_fn, plan, params, carry, xs_chunks = _prepare(sim)
    errs_d, upds_d = [], []
    rec = 0
    with jax.profiler.TraceAnnotation("sweep.dispatch"):
        for xs in xs_chunks:
            if rec >= plan.n_rec_live:
                break            # every row is past its horizon: dead chunk
            carry, (e, u) = chunk_fn(params, carry, xs)
            errs_d.append(e)
            upds_d.append(u)
            rec += e.shape[0]
    with jax.profiler.TraceAnnotation("sweep.fetch"):
        final, errs_rec, upds_rec = jax.device_get(
            jax.block_until_ready((carry, errs_d, upds_d)))
    with jax.profiler.TraceAnnotation("sweep.results"):
        return _assemble(sim, plan, final, errs_rec, upds_rec)


def _assemble(sim, plan, final, errs_rec, upds_rec) -> List[SimResult]:
    """The batch's results from the fetched traces and final state."""
    B = sim.B
    err_t = np.concatenate(errs_rec)[:plan.n_rec_live, :B]
    upd_t = np.concatenate(upds_rec)[:plan.n_rec_live, :B]

    # select the measurement grid: value at m_j = state after the first
    # tick t with m_j ≤ t + eps (the numpy engine's while-loop rule);
    # the planner guarantees that tick lands on a supertick record.
    # Plus the t = 0 point (w = 0 ⇒ normalized error exactly 1).
    r_idx = (_measure_idx(sim) + 1) // plan.stride - 1
    errs = np.concatenate([np.ones((B, 1)),
                           np.asarray(err_t, np.float64).T[:, r_idx]],
                          axis=1)
    upds = np.concatenate([np.zeros((B, 1), np.int64),
                           np.asarray(upd_t, np.int64).T[:, r_idx]], axis=1)

    # write final state back so SimResult assembly is shared with numpy
    sim.w = np.asarray(final["w"][:B], np.float64)
    sim.steps = np.asarray(final["steps"][:B], np.int64)
    sim.alive = np.asarray(final["alive"][:B])
    sim.total_updates = np.asarray(final["total_updates"][:B], np.int64)
    sim.control_messages = np.asarray(final["control"][:B], np.int64)
    if "pol_thr" in final:
        sim.pol_thr = np.asarray(final["pol_thr"][:B], np.int64)
        sim.pol_ema = np.asarray(final["pol_ema"][:B], np.float64)
        sim.pol_beta = np.asarray(final["pol_beta"][:B], np.int64)
    return sim._results(errs, upds)
