"""Step-function assembly shared by the trainer, server and dry-run.

Builds jit-ready ``train_step`` / ``prefill_step`` / ``serve_step`` (and the
PSP-barrier train step) for a (ModelConfig, InputShape, Mesh) combination,
together with the abstract (ShapeDtypeStruct + sharding) input trees the
dry-run lowers against.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig
from repro.core.spmd_psp import PSPConfig, PSPState, psp_train_step
from repro.data.synthetic import make_batch_specs
from repro.models import (cache_defs, decode_step, loss_fn, model_defs,
                          prefill)
from repro.models.params import ParamDef, abstract_params, spec_tree
from repro.optim import Optimizer, apply_updates, clip_by_norm
from repro.parallel.sharding import AxisRules, make_rules, use_rules

PyTree = Any


# --------------------------------------------------------------------------- #
# step functions
# --------------------------------------------------------------------------- #
def make_train_step(cfg: ModelConfig, optimizer: Optimizer,
                    rules: Optional[AxisRules] = None,
                    clip_norm: Optional[float] = 1.0) -> Callable:
    """The plain step ``(params, opt_state, batch) -> (params, opt_state,
    loss, metrics)``; the device scopes ``train.grads`` (forward, backward,
    clipping) and ``train.optimizer`` (the update) name its two phases in a
    profiler trace."""
    def train_step(params, opt_state, batch):
        with use_rules(rules):
            with jax.named_scope("train.grads"):
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, batch, cfg)
                if clip_norm is not None:
                    grads = clip_by_norm(grads, clip_norm)
            with jax.named_scope("train.optimizer"):
                updates, opt_state = optimizer.update(grads, opt_state,
                                                      params)
                params = apply_updates(params, updates)
        return params, opt_state, loss, metrics
    return train_step


def make_prefill_step(cfg: ModelConfig,
                      rules: Optional[AxisRules] = None) -> Callable:
    def prefill_step(params, batch):
        with use_rules(rules):
            logits, cache = prefill(params, batch["tokens"], cfg,
                                    embeds=batch.get("embeds"))
        return logits, cache
    return prefill_step


def make_serve_step(cfg: ModelConfig,
                    rules: Optional[AxisRules] = None) -> Callable:
    def serve_step(params, cache, batch):
        with use_rules(rules):
            logits, new_cache = decode_step(params, cache, batch["tokens"],
                                            cfg)
        return logits, new_cache
    return serve_step


def make_psp_train_step(cfg: ModelConfig, psp_cfg: PSPConfig,
                        optimizer: Optimizer,
                        rules: Optional[AxisRules] = None,
                        clip_norm: Optional[float] = 1.0) -> Callable:
    """PSP-barrier training: W worker views, masked server aggregation."""
    def grad_fn(params, microbatch):
        with jax.named_scope("train.grads"):
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, microbatch, cfg)
            if clip_norm is not None:
                grads = clip_by_norm(grads, clip_norm)
            return loss, grads

    def step(state: PSPState, batch):
        with use_rules(rules):
            return psp_train_step(psp_cfg, grad_fn, optimizer.update,
                                  state, batch)
    return step


# --------------------------------------------------------------------------- #
# abstract inputs for the dry-run
# --------------------------------------------------------------------------- #
def abstract_opt_state(optimizer_name: str, defs: Dict,
                       rules: Optional[AxisRules]) -> Dict:
    step = jax.ShapeDtypeStruct((), jnp.int32)
    if optimizer_name == "sgd":
        return {"step": step}
    mu = abstract_params(defs, jnp.float32, rules)
    if optimizer_name == "momentum":
        return {"step": step, "mu": mu}
    nu = abstract_params(defs, jnp.float32, rules)
    return {"step": step, "mu": mu, "nu": nu}


def abstract_cache(cfg: ModelConfig, shape: InputShape,
                   rules: Optional[AxisRules]) -> Dict:
    """Decode-shape cache: capacity seq_len, holding seq_len−1 tokens."""
    cdefs = cache_defs(cfg, shape.global_batch, shape.seq_len)
    return abstract_params(cdefs, jnp.bfloat16, rules)


def dryrun_inputs(cfg: ModelConfig, shape: InputShape, rules: AxisRules,
                  optimizer_name: str = "adamw"
                  ) -> Tuple[tuple, Callable, Tuple[int, ...]]:
    """(abstract_args, step_fn, donate_argnums) for one dry-run combo.

    Donation mirrors production: train donates (params, opt_state); decode
    donates the KV cache (without it XLA double-buffers the cache and the
    32k-decode combos of the big-KV archs exceed the 16 GB chip).
    """
    defs = model_defs(cfg)
    aparams = abstract_params(defs, jnp.dtype(cfg.param_dtype), rules)
    if shape.kind == "train":
        from repro.optim import adamw
        opt = adamw(1e-4)
        astate = abstract_opt_state(optimizer_name, defs, rules)
        batch = make_batch_specs(cfg, shape, rules)
        return (aparams, astate, batch), make_train_step(cfg, opt, rules),             (0, 1)
    if shape.kind == "prefill":
        batch = make_batch_specs(cfg, shape, rules)
        return (aparams, batch), make_prefill_step(cfg, rules), ()
    # decode
    cache = abstract_cache(cfg, shape, rules)
    batch = make_batch_specs(cfg, shape, rules, kind="decode")
    return (aparams, cache, batch), make_serve_step(cfg, rules), (1,)
