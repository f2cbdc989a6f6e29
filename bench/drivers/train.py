"""Trainer cells: the jitted step that ``repro.launch.train`` builds.

``barrier: "none"`` drives the plain synchronous (pjit) step,
``jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1))``; any other
barrier drives the PSP tick ``make_psp_step(cfg, pcfg, opt)`` with its
donated ``PSPState``.  The model is built from the configuration file's
sizes; weights come from ``--seed`` in one jitted call on the device.

Set-up builds the step and its state, then drives it through the first
``check_ticks`` ticks with the window's own feed: those ticks compile the
step and are the ticks the reference follows.  After each, it keeps on the
host what the comparison needs before the next tick consumes the donated
state: the tick's loss, the per-worker step counters and push flags, the
per-leaf norms of the first gradient the optimizer got (its first moment
after that tick, over ``1 − β₁``), and after the last one the per-leaf
norms of the change of the server parameters.

The window then runs ticks for ``--seconds``: each tick draws its tokens
on the host, calls the step and reads its metrics back, which ends it.
"""
from __future__ import annotations

import functools
import gc
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

import compare  # noqa: E402
import files  # noqa: E402
import traffic  # noqa: E402


#: the activation each of the program's MLP types applies, as published
#: configs name it (its gelu is the tanh approximation)
MLP_ACT = {"swiglu": ("silu",), "geglu": ("gelu_pytorch_tanh", "gelu_new"),
           "gelu": ("gelu_pytorch_tanh", "gelu_new")}
#: what the configuration file states of the architecture beyond its
#: sizes; each is required, none has a default
ARCH_KEYS = ("family", "layer_pattern", "mlp_type", "qkv_bias", "head_dim",
             "hidden_act", "tie_word_embeddings")


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file.

    Sizes and architecture both come from the file: a configuration that
    leaves out one of :data:`ARCH_KEYS`, or names an MLP type or an
    activation that do not go together, is refused.
    """
    from repro.configs.base import ModelConfig
    missing = [k for k in ARCH_KEYS if k not in cfg]
    if missing:
        raise ValueError(f"configuration {cfg['name']!r} does not state "
                         f"{', '.join(missing)}")
    mlp, act = cfg["mlp_type"], cfg["hidden_act"]
    if act not in MLP_ACT.get(mlp, ()):
        raise ValueError(f"configuration {cfg['name']!r}: no known MLP "
                         f"{mlp!r} with activation {act!r}")
    return ModelConfig(
        name=cfg["name"], family=cfg["family"],
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], qkv_bias=cfg["qkv_bias"],
        layer_pattern=tuple(cfg["layer_pattern"]), mlp_type=mlp,
        rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"],
        norm_eps=cfg["rms_norm_eps"], dtype=cfg["compute_dtype"],
        param_dtype=cfg["param_dtype"])


def optimizer(wl: dict):
    """The trainer's optimizer as ``repro.launch.train`` builds it."""
    from repro.optim import adamw, warmup_cosine
    n = wl["schedule_steps"]
    return adamw(warmup_cosine(wl["lr"], n // 10 + 1, n), b1=wl["adam_b1"],
                 b2=wl["adam_b2"], eps=wl["adam_eps"])


def leaf_sq(tree) -> jax.Array:
    """f32[leaves] sums of squares, leaf by leaf."""
    return jnp.stack([jnp.sum(jnp.square(x.astype(jnp.float32)))
                      for x in jax.tree.leaves(tree)])


class Session:
    """One cell's compiled step, its state, and what its check needs."""

    def __init__(self, wl: dict, cfg: dict, seed: int):
        from repro.launch.steps import make_train_step
        from repro.launch.train import make_psp_step
        from repro.models import init_model
        self.wl, self.cfg, self.seed = wl, cfg, seed
        mc = model_config(cfg)
        self.psp = wl["barrier"] != "none"
        self.W = wl["workers"] if self.psp else 1
        self.shape = ((self.W, wl["batch"], wl["seq"]) if self.psp
                      else (wl["batch"], wl["seq"]))
        self.tokens_per_push = wl["batch"] * wl["seq"]
        key = jax.random.PRNGKey(traffic.seed32(seed))
        opt = optimizer(wl)
        init = jax.jit(functools.partial(init_model, mc))
        self._delta = jax.jit(lambda p, k: leaf_sq(
            jax.tree.map(jnp.subtract, p, init(k))))
        self._mu = jax.jit(lambda mu: (leaf_sq(mu), [
            compare.take_sample(x) for x in jax.tree.leaves(mu)]))
        self.key = key
        params = init(key)
        if self.psp:
            from repro.core.spmd_psp import PSPConfig, psp_init
            self.pcfg = PSPConfig(
                barrier=wl["barrier"], n_workers=self.W,
                sample_size=wl["sample_size"], staleness=wl["staleness"],
                straggler_frac=wl["straggler_frac"],
                base_compute=wl["base_compute"],
                compute_jitter=wl["compute_jitter"],
                straggler_slowdown=wl["straggler_slowdown"],
                poll_interval=wl["poll_interval"])
            pkey = jax.random.PRNGKey(wl["psp_seed"])
            self.state = jax.jit(
                lambda p, k: psp_init(self.pcfg, p, opt.init, k),
                donate_argnums=(0,))(params, pkey)
            self.step = make_psp_step(mc, self.pcfg, opt)
        else:
            self.state = (params, jax.jit(opt.init)(params))
            self.step = jax.jit(make_train_step(mc, opt),
                                donate_argnums=(0, 1))
        del params
        self.tick = 0
        self.seen = {"loss": [], "steps": [], "pushed": [], "pushes": [],
                     "grad_sq": None, "delta_sq": None}
        for _ in range(wl["check_ticks"]):
            self._check_tick()
        self.seen["delta_sq"] = np.asarray(
            self._delta(self._server(), key)).tolist()

    # ------------------------------------------------------------------ #
    def _server(self):
        return self.state.server_params if self.psp else self.state[0]

    def _opt_mu(self):
        return (self.state.opt_state if self.psp else self.state[1])["mu"]

    def _feed(self):
        toks = traffic.tokens(self.seed, self.tick, self.shape,
                              self.cfg["vocab_size"], self.wl["tokens"])
        return jnp.asarray(toks)

    def _call(self, toks):
        """Dispatch one tick; returns its (loss, pushes) on the device."""
        if self.psp:
            self.state, m = self.step(self.state, toks)
            return m["loss"], m["pushes"]
        params, opt_state = self.state
        params, opt_state, loss, _ = self.step(params, opt_state,
                                               {"tokens": toks})
        self.state = (params, opt_state)
        return loss, 1

    @staticmethod
    def _read(out) -> tuple[float, int]:
        """The tick's metrics on the host, which ends the tick."""
        loss, pushes = jax.device_get(out)
        return float(loss), int(pushes)

    def _check_tick(self) -> None:
        loss, pushes = self._read(self._call(self._feed()))
        self.tick += 1
        s = self.seen
        s["loss"].append(loss)
        s["pushes"].append(pushes)
        if self.psp:
            step, pushed = jax.device_get((self.state.step,
                                           self.state.pushed))
            s["steps"].append(np.asarray(step).tolist())
            s["pushed"].append(np.asarray(pushed).tolist())
        if pushes and s["grad_sq"] is None:
            c = 1.0 / (1.0 - self.wl["adam_b1"])
            mu_sq, mu_s = jax.device_get(self._mu(self._opt_mu()))
            s["grad_sq"] = (np.asarray(mu_sq, np.float64) * c * c).tolist()
            s["grad_sample"] = [np.asarray(x, np.float64) * c for x in mu_s]

    # ------------------------------------------------------------------ #
    def _ticks(self, seconds: float) -> dict:
        tick_s, parts, pushes = [], [], 0
        t_first = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.data"):
                toks = self._feed()
            ta = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.step"):
                out = self._call(toks)
            tb = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.read"):
                _, p = self._read(out)
            self.tick += 1
            t1 = time.perf_counter()
            tick_s.append(t1 - t0)
            parts.append({"data": ta - t0, "step": tb - ta, "read": t1 - tb})
            pushes += p
            if t1 - t_first >= seconds:
                break
        return {"ticks": len(tick_s), "pushes": pushes,
                "elapsed": t1 - t_first, "call_s": tick_s,
                "call_parts": parts}

    def window(self, seconds: float) -> dict:
        """Ticks for ``seconds``; the end-to-end metrics and counters."""
        w = self._ticks(seconds)
        landed = w["pushes"] * self.tokens_per_push
        p95 = float(np.percentile(np.asarray(w["call_s"]) * 1e3, 95))
        w.update(
            landed_tokens=landed, workers=self.W,
            attempted=w["ticks"], failed=0,
            metrics={"landed_tokens_per_s": {
                         "value": landed / w["elapsed"], "unit": "tokens/s"},
                     "step_p95_ms": {"value": p95, "unit": "ms"}})
        return w

    def traced(self, trace_dir: str, seconds: float) -> dict:
        """A further ``seconds`` of ticks under the profiler, reduced."""
        trace = files.module("trace.py")
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        try:
            self._ticks(seconds)
        finally:
            jax.profiler.stop_trace()
        try:
            return trace.reduce(trace.find_xplane(trace_dir), 1)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # ------------------------------------------------------------------ #
    def check(self) -> dict:
        """Frees the program's state, runs the reference, compares;
        ``{name: (reading, limit)}``."""
        self.state = None
        self.step = None
        gc.collect()
        want = _reference(self.wl).replay(self.cfg, self.wl, self.seed)
        return compare.checks(self.wl, compare.train_readings(
            self.wl, self.seen, want))


def _reference(wl: dict):
    """The configuration's plain reference module."""
    return files.module("configs", f"{wl['config']}.ref.py")


def setup(wl: dict, cfg: dict, seed: int) -> Session:
    """The cell's session, set up and through its checked ticks."""
    return Session(wl, cfg, seed)
