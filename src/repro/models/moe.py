"""Mixture-of-Experts block: top-k router + expert-parallel FFN.

Design (DESIGN.md §7, TPU adaptation):

* **Routing** is computed locally per data shard (the router weight is
  replicated — it is tiny).
* **Experts are sharded over the `model` axis** (expert parallelism).  Inside
  a ``shard_map`` over the full mesh, every model shard sees its data row's
  tokens (tokens are *replicated* across the model axis), computes only its
  local experts at fixed capacity, and the outputs are combined with a single
  ``psum`` over `model` — the same collective shape as ordinary tensor
  parallelism, i.e. **no all-to-all is needed** in this scheme.  (An a2a
  dispatch variant is evaluated in EXPERIMENTS.md §Perf.)
* **Capacity**: per (data-shard × expert) capacity C = ceil(T_loc·k/E · cf);
  over-capacity tokens are dropped (standard Switch-style behaviour) and the
  drop fraction is part of the aux metrics.
* The per-expert FF dim is FSDP-sharded over `data` at rest and
  all-gathered per layer (see ``transformer._gather_moe``).

The same ``_moe_core`` runs unsharded for CPU smoke tests (≤4 experts).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.params import ParamDef
from repro.parallel.sharding import constrain, current_rules

__all__ = ["moe_defs", "moe_apply"]


def moe_defs(cfg) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": ParamDef((d, e), (None, None)),   # replicated (tiny)
        "w_gate": ParamDef((e, d, f), ("experts_w", None, "expert_ff_w")),
        "w_up": ParamDef((e, d, f), ("experts_w", None, "expert_ff_w")),
        "w_down": ParamDef((e, f, d), ("experts_w", "expert_ff_w", None)),
    }


def _capacity(tokens_local: int, cfg) -> int:
    c = int(np.ceil(tokens_local * cfg.n_experts_per_token / cfg.n_experts
                    * cfg.moe_capacity_factor))
    return max(8, ((c + 7) // 8) * 8)   # pad to 8 for TPU-friendly layout


def _moe_core(x: jax.Array, router: jax.Array, w_gate: jax.Array,
              w_up: jax.Array, w_down: jax.Array, cfg,
              first_expert, capacity: int) -> Tuple[jax.Array, jax.Array]:
    """Token-choice top-k MoE over the local expert slice.

    x: (T, D) local tokens; w_*: (E_loc, ...) local experts;
    first_expert: global index of the first local expert.
    Returns (y: (T, D) partial output over local experts, aux_loss: f32[]).
    """
    T, D = x.shape
    E = cfg.n_experts
    E_loc = w_gate.shape[0]
    k = cfg.n_experts_per_token
    dtype = x.dtype

    logits = (x @ router.astype(dtype)).astype(jnp.float32)   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)                    # (T, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)    # renormalise

    # --- aux load-balance loss (Switch-style) -------------------------- #
    counts = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    frac_tokens = counts / (T * k)
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)

    # --- sort-based dispatch ------------------------------------------- #
    flat_e = top_i.reshape(-1)                                # (T·k,)
    flat_t = jnp.repeat(jnp.arange(T), k)
    flat_w = top_p.reshape(-1)
    order = jnp.argsort(flat_e)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    group_start = jnp.searchsorted(se, jnp.arange(E))         # (E,)
    pos = jnp.arange(T * k) - group_start[se]
    loc = se - first_expert
    ok = (loc >= 0) & (loc < E_loc) & (pos < capacity)
    slot = jnp.where(ok, loc * capacity + pos, E_loc * capacity)

    buf = jnp.zeros((E_loc * capacity + 1, D), dtype)
    buf = buf.at[slot].set(x[st])
    h = buf[: E_loc * capacity].reshape(E_loc, capacity, D)

    g = jnp.einsum("ecd,edf->ecf", h, w_gate.astype(dtype))
    u = jnp.einsum("ecd,edf->ecf", h, w_up.astype(dtype))
    a = jax.nn.silu(g) * u
    o = jnp.einsum("ecf,efd->ecd", a, w_down.astype(dtype))
    o_flat = o.reshape(E_loc * capacity, D)

    contrib = jnp.where(ok, sw, 0.0).astype(dtype)[:, None] * \
        o_flat[jnp.minimum(slot, E_loc * capacity - 1)]
    y = jnp.zeros((T, D), dtype).at[st].add(
        jnp.where(ok[:, None], contrib, 0))
    return y, aux


def moe_apply(p: dict, x: jax.Array, cfg,
              mesh=None) -> Tuple[jax.Array, jax.Array]:
    """MoE FFN. x: (B, S, D) → (y, aux_loss).

    With a mesh: expert-parallel shard_map (experts over `model`, tokens over
    `pod`×`data`).  Without: single-shard fallback (smoke tests).
    """
    B, S, D = x.shape
    rules = current_rules()
    mesh = mesh or (rules.mesh if rules is not None else None)
    use_shmap = (mesh is not None and "model" in mesh.axis_names
                 and int(mesh.shape["model"]) > 1
                 and cfg.n_experts % int(mesh.shape["model"]) == 0)

    if not use_shmap:
        cap = _capacity(B * S, cfg)
        y, aux = _moe_core(x.reshape(B * S, D), p["router"], p["w_gate"],
                           p["w_up"], p["w_down"], cfg, 0, cap)
        return y.reshape(B, S, D), aux

    # make the per-layer expert weights whole along the FSDP dim before
    # entering shard_map (XLA inserts the all-gather over `data`)
    wg = constrain(p["w_gate"], ("experts_w", None, None))
    wu = constrain(p["w_up"], ("experts_w", None, None))
    wd = constrain(p["w_down"], ("experts_w", None, None))

    names = mesh.axis_names
    batch_axes = tuple(a for a in ("pod", "data") if a in names)
    dp = int(np.prod([mesh.shape[a] for a in batch_axes])) if batch_axes else 1
    t_loc = (B // dp) * S
    cap = _capacity(t_loc, cfg)

    def local(xl, router, wgl, wul, wdl):
        # xl: (B_loc, S, D) — replicated over `model` within a data row
        bl = xl.shape[0]
        first = jax.lax.axis_index("model") * (cfg.n_experts //
                                               int(mesh.shape["model"]))
        y, aux = _moe_core(xl.reshape(bl * S, D), router, wgl, wul, wdl,
                           cfg, first, cap)
        y = jax.lax.psum(y, "model")
        # aux is identical across `model` (same tokens, replicated router) —
        # average it over the data axes only
        if batch_axes:
            aux = jax.lax.psum(aux, axis_name=batch_axes) / dp
        return y.reshape(bl, S, D), aux

    y, aux = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(batch_axes or None, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(batch_axes or None, None, None), P()),
    )(x, p["router"], wg, wu, wd)
    return y, aux
