"""Logical-axis sharding rules (MaxText-style) for the production mesh.

Arrays in the model are annotated with *logical* axis names; a rules table
maps each logical name to an ordered preference of mesh axes.  At constraint
time, mesh axes that (a) don't exist in the current mesh, (b) don't divide
the dimension, or (c) were already consumed by an earlier dim of the same
array, are dropped — so a single rules table covers every architecture
(e.g. ``heads→model`` silently degrades to replicated for archs whose head
count doesn't divide the 16-way model axis, and the rules table then routes
attention balance through ``attn_batch``/``qseq`` instead; see
DESIGN.md §7 and the per-arch notes in EXPERIMENTS.md).

The table is built per (ModelConfig, InputShape, Mesh) by :func:`make_rules`.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

__all__ = ["AxisRules", "PSP_WORKER_AXES", "SWEEP_NODES_AXIS",
           "SWEEP_ROWS_AXIS", "constrain", "current_rules", "make_rules",
           "psp_worker_axes", "spec_for", "sweep_mesh", "use_rules"]

MeshAxes = Tuple[str, ...]

# --------------------------------------------------------------------------- #
# shared mesh-axis vocabulary
#
# Every engine that lays PSP state over devices names its axes from this
# table, so the trainer and the sweep engines cannot drift into
# incompatible sharding conventions:
#
# * the sweep engines (:mod:`repro.core.vector_sim_jax`) run a 2-D
#   ``(rows, nodes)`` mesh — scenario rows over SWEEP_ROWS_AXIS, each
#   scenario's P node slots over SWEEP_NODES_AXIS;
# * the SPMD trainer (:mod:`repro.core.spmd_psp`) carries its worker
#   dimension W on PSP_WORKER_AXES (the server psum reduces over exactly
#   these axes), resolved against the production mesh by
#   :func:`psp_worker_axes`.
# --------------------------------------------------------------------------- #

#: scenario-row axis of the sweep engines' 2-D mesh
SWEEP_ROWS_AXIS = "rows"

#: node-slot axis of the sweep engines' 2-D mesh (the P dimension)
SWEEP_NODES_AXIS = "nodes"

#: mesh axes that may carry the SPMD trainer's worker dimension, in
#: major-to-minor order (a multi-pod worker is a (pod, data-row) pair)
PSP_WORKER_AXES: MeshAxes = ("pod", "data")


def sweep_mesh(rows: int, nodes: int = 1) -> Mesh:
    """The sweep engines' ``(rows, nodes)`` device mesh.

    The first ``rows × nodes`` local devices, rows-major — the planner
    (:mod:`repro.core.sweep_plan`) guarantees the product fits the host.
    The degenerate ``(1, 1)`` mesh is the single-device engine.
    """
    dev = np.array(jax.devices()[:rows * nodes]).reshape(rows, nodes)
    return Mesh(dev, (SWEEP_ROWS_AXIS, SWEEP_NODES_AXIS))


def psp_worker_axes(mesh: Optional[Mesh]) -> MeshAxes:
    """The mesh axes carrying the trainer's worker dimension W.

    :data:`PSP_WORKER_AXES` filtered to the axes the mesh actually has —
    the single definition both the dry-run's ``psp_workers`` rules entry
    and batch specs resolve through.
    """
    if mesh is None:
        return ()
    return tuple(a for a in PSP_WORKER_AXES if a in mesh.axis_names)


class AxisRules:
    """Logical-name → mesh-axes mapping with divisibility-aware resolution."""

    def __init__(self, table: Dict[str, MeshAxes], mesh: Optional[Mesh]):
        self.table = dict(table)
        self.mesh = mesh

    # ------------------------------------------------------------------ #
    def mesh_axis_size(self, axis: str) -> int:
        if self.mesh is None or axis not in self.mesh.shape:
            return 0
        return int(self.mesh.shape[axis])

    def spec(self, logical_axes: Sequence[Optional[str]],
             shape: Sequence[int]) -> P:
        """Resolve logical axes to a PartitionSpec for a concrete shape."""
        used: set = set()
        out = []
        for dim, name in zip(shape, logical_axes):
            if name is None or name not in self.table:
                out.append(None)
                continue
            picked = []
            prod = 1
            for ax in self.table[name]:
                size = self.mesh_axis_size(ax)
                if size == 0 or ax in used:
                    continue
                if dim % (prod * size) == 0:
                    picked.append(ax)
                    prod *= size
            used.update(picked)
            out.append(tuple(picked) if picked else None)
        return P(*out)

    def sharding(self, logical_axes, shape) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.spec(logical_axes, shape))


# --------------------------------------------------------------------------- #
# thread-local active rules (so model code can annotate without plumbing)
# --------------------------------------------------------------------------- #
_state = threading.local()


@contextlib.contextmanager
def use_rules(rules: Optional[AxisRules]):
    prev = getattr(_state, "rules", None)
    _state.rules = rules
    try:
        yield rules
    finally:
        _state.rules = prev


def current_rules() -> Optional[AxisRules]:
    return getattr(_state, "rules", None)


def spec_for(logical_axes, shape) -> P:
    rules = current_rules()
    if rules is None:
        return P()
    return rules.spec(logical_axes, shape)


def constrain(x: jax.Array, logical_axes: Sequence[Optional[str]]) -> jax.Array:
    """``with_sharding_constraint`` by logical names; no-op without rules."""
    rules = current_rules()
    if rules is None or rules.mesh is None:
        return x
    spec = rules.spec(logical_axes, x.shape)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, spec))


# --------------------------------------------------------------------------- #
# rules tables
# --------------------------------------------------------------------------- #
def make_rules(cfg, shape, mesh: Optional[Mesh]) -> AxisRules:
    """Build the rules table for one (arch, input-shape, mesh) combination.

    Arguments may be None-ish duck types in tests; ``cfg`` needs
    ``n_heads``/``n_kv_heads``; ``shape`` needs ``kind``/``global_batch``.
    """
    data_axes: MeshAxes = ()
    model = 16
    if mesh is not None:
        # the rules place arrays by sharding constraints, which only Auto
        # mesh axes accept; jax.make_mesh gives Explicit axes by default
        mesh = Mesh(mesh.devices, mesh.axis_names,
                    axis_types=(AxisType.Auto,) * len(mesh.axis_names))
        names = mesh.axis_names
        data_axes = tuple(a for a in ("pod", "data") if a in names)
        model = int(mesh.shape.get("model", 1))

    heads_divisible = (cfg.n_heads % max(model, 1) == 0)

    table: Dict[str, MeshAxes] = {
        # activations
        "batch": data_axes,
        "seq": (),
        "qseq": (),
        "heads": ("model",),
        "kv_heads": ("model",),
        "d_model": (),              # activations keep d_model unsharded
        "d_ff_act": ("model",),
        "experts_act": ("model",),
        "vocab_act": ("model",),
        "d_inner_act": ("model",),
        "ssm_heads_act": ("model",),
        "lru_act": ("model",),
        # weights (FSDP dim = 'data'; tensor dim = 'model')
        "d_model_w": ("data",),
        "heads_w": ("model",),
        "kv_heads_w": ("model",),
        "d_ff_w": ("model",),
        "vocab_w": ("model",),
        "experts_w": ("model",),
        "expert_ff_w": ("data",),   # FSDP the per-expert FF dim (see moe.py)
        "d_inner_w": ("model",),
        "ssm_heads_w": ("model",),
        "lru_w": ("model",),
        "layers": (),
        "conv": (),
        "state": (),
        # kv-cache layout (decode)
        "cache_seq": (),
        "cache_batch": data_axes,
    }

    kind = getattr(shape, "kind", "train")
    gbatch = getattr(shape, "global_batch", 0)

    # attention activations: batch over data axes; heads over model (archs
    # whose head count doesn't divide the model axis are zero-padded to the
    # next multiple inside attn_apply, so `heads` is always shardable)
    table["attn_batch"] = data_axes

    if kind == "decode":
        if gbatch == 1:
            # long_500k: batch unshardable — spread the cache over everything
            table["cache_seq"] = data_axes + ("model",)
        else:
            table["cache_seq"] = ("model",)

    return AxisRules(table, mesh)


def _sz(mesh, axis):
    if mesh is None:
        return 1
    return int(mesh.shape.get(axis, 1))
