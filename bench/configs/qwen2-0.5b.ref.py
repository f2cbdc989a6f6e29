"""Plain reference for qwen2-0.5b training: float32, no kernels, no cache.

Follows the published architecture (arXiv:2407.10671; Hugging Face
``Qwen2ForCausalLM``): token embedding; per layer RMSNorm → grouped-query
attention with biased q/k/v projections, rotary positions (half-rotation,
θ from the configuration) and a causal softmax → residual; RMSNorm → SwiGLU
MLP → residual; a final RMSNorm; logits against the tied embedding; mean
next-token cross-entropy.  Every matrix product runs at
``Precision.HIGHEST``.  Layers are scanned and rematerialised, which
changes no number.

Departures, each shared with the trainer under test by design of the
benchmark: weights are random, drawn from the seed as the trainer's
initializer lays them out (normal(0, 0.02) matrices, unit norms, zero
biases, leaf ``i`` of the sorted parameter tree keyed by ``fold_in(key,
i)``), and the gate and up projections of the MLP are held as one
``(d, ff, 2)`` array.

:func:`replay` re-runs a trainer cell's first ticks from the seed: the
tokens come from the harness's generator; for a PSP cell the virtual clock
(seeded step durations, stragglers, polls) and the barrier are stepped
here, for β ≥ W − 1 (every peer is sampled); gradients are clipped per
worker to global norm 1 and averaged over the pushing workers; Adam with
the warm-up-cosine schedule updates the server parameters.

``quant="fp8"`` is the control: every matrix product takes its operands
rounded to float8 e4m3 with one scale per tensor (straight-through in the
backward pass).
"""
from __future__ import annotations

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

import traffic  # noqa: E402

HI = lax.Precision.HIGHEST
FP8_MAX = 448.0


# --------------------------------------------------------------------------- #
# parameters
# --------------------------------------------------------------------------- #
#: the architecture this reference implements, as the configuration file
#: states it; any other is refused, not approximated
ARCHITECTURE = {"family": "dense", "layer_pattern": ["attn"],
                "mlp_type": "swiglu", "hidden_act": "silu", "qkv_bias": True,
                "tie_word_embeddings": True}


def check_architecture(cfg: dict) -> None:
    """Raises unless ``cfg`` states the architecture written here."""
    wrong = {k: cfg.get(k) for k, v in ARCHITECTURE.items()
             if cfg.get(k) != v}
    if wrong:
        raise ValueError(f"the qwen2 reference does not implement {wrong}")


def param_layout(cfg: dict) -> list[tuple[str, tuple, str]]:
    """(path, shape, init) of every parameter, in sorted-path order."""
    check_architecture(cfg)
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, ff, v = cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"]
    return [
        ("embed", (v, d), "normal"),
        ("final_norm", (d,), "ones"),
        ("groups/0/attn/bk", (L, kv, hd), "zeros"),
        ("groups/0/attn/bq", (L, h, hd), "zeros"),
        ("groups/0/attn/bv", (L, kv, hd), "zeros"),
        ("groups/0/attn/wk", (L, d, kv, hd), "normal"),
        ("groups/0/attn/wo", (L, h, hd, d), "normal"),
        ("groups/0/attn/wq", (L, d, h, hd), "normal"),
        ("groups/0/attn/wv", (L, d, kv, hd), "normal"),
        ("groups/0/ln1", (L, d), "ones"),
        ("groups/0/ln2", (L, d), "ones"),
        ("groups/0/mlp/w_down", (L, ff, d), "normal"),
        ("groups/0/mlp/w_gu", (L, d, ff, 2), "normal"),
    ]


def init_params(cfg: dict, key: jax.Array) -> dict:
    """{path: f32 array}: the seed's weights."""
    out = {}
    for i, (path, shape, init) in enumerate(param_layout(cfg)):
        if init == "zeros":
            out[path] = jnp.zeros(shape, jnp.float32)
        elif init == "ones":
            out[path] = jnp.ones(shape, jnp.float32)
        else:
            k = jax.random.fold_in(key, i)
            out[path] = (0.02 * jax.random.normal(k, shape)).astype(
                jnp.float32)
    return out


# --------------------------------------------------------------------------- #
# forward and loss
# --------------------------------------------------------------------------- #
def _fp8(x: jax.Array) -> jax.Array:
    """``x`` rounded to float8 e4m3 under one per-tensor scale."""
    s = lax.stop_gradient(FP8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30))
    r = (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    return x + lax.stop_gradient(r - x)


def _mm(eq: str, a, b, quant: str | None):
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HI)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Half-rotation rotary embedding; x: (B, S, H, hd)."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freq = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
    ang = np.arange(s, dtype=np.float32)[:, None] * freq
    cos = jnp.asarray(np.cos(ang))[:, None, :]
    sin = jnp.asarray(np.sin(ang))[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def ce_sum(params: dict, tokens: jax.Array, cfg: dict,
           quant: str | None = None) -> jax.Array:
    """Summed next-token cross-entropy of ``tokens`` (B, S)."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    S = tokens.shape[1]
    x = params["embed"][tokens]
    causal = np.tril(np.ones((S, S), bool))
    layers = {k.split("/", 2)[2]: v for k, v in params.items()
              if k.startswith("groups/")}

    def layer(x, p):
        y = _rms(x, p["ln1"], eps)
        q = _mm("bsd,dhk->bshk", y, p["attn/wq"], quant) + p["attn/bq"]
        k = _mm("bsd,dhk->bshk", y, p["attn/wk"], quant) + p["attn/bk"]
        v = _mm("bsd,dhk->bshk", y, p["attn/wv"], quant) + p["attn/bv"]
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
        s = _mm("bqhd,bkhd->bhqk", q, k, quant) * (q.shape[-1] ** -0.5)
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = _mm("bhqk,bkhd->bqhd", a, v, quant)
        x = x + _mm("bshk,hkd->bsd", o, p["attn/wo"], quant)
        y = _rms(x, p["ln2"], eps)
        gu = _mm("bsd,dft->bsft", y, p["mlp/w_gu"], quant)
        m = jax.nn.silu(gu[..., 0]) * gu[..., 1]
        return x + _mm("bsf,fd->bsd", m, p["mlp/w_down"], quant), None

    x, _ = lax.scan(jax.checkpoint(layer), x, layers)
    x = _rms(x, params["final_norm"], eps)
    logits = _mm("bsd,vd->bsv", x[:, :-1], params["embed"], quant)
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)


@functools.lru_cache(maxsize=None)
def _grad_fn(cfg_items: tuple, quant: str | None, grad: bool = True):
    cfg = dict(cfg_items)
    f = functools.partial(ce_sum, cfg=cfg, quant=quant)
    return jax.jit(jax.value_and_grad(f) if grad else f)


def _items(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if not isinstance(v, (list, dict))))


def loss_only(params: dict, tokens: np.ndarray, cfg: dict, block: int,
              quant: str | None = None) -> float:
    """Mean cross-entropy of ``tokens``, in row blocks, no gradient."""
    f = _grad_fn(_items(cfg), quant, grad=False)
    n = tokens.shape[0] * (tokens.shape[1] - 1)
    return sum(float(f(params, jnp.asarray(tokens[i:i + block])))
               for i in range(0, tokens.shape[0], block)) / n


def loss_and_grad(params: dict, tokens: np.ndarray, cfg: dict, block: int,
                  quant: str | None = None):
    """Mean cross-entropy of ``tokens`` and its gradient, in row blocks."""
    f = _grad_fn(_items(cfg), quant)
    n = tokens.shape[0] * (tokens.shape[1] - 1)
    total, grad = 0.0, None
    for i in range(0, tokens.shape[0], block):
        lsum, g = f(params, jnp.asarray(tokens[i:i + block]))
        total += float(lsum)
        grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
        del g
    return total / n, jax.tree.map(lambda g: g / n, grad)


# --------------------------------------------------------------------------- #
# optimizer
# --------------------------------------------------------------------------- #
def lr_at(wl: dict, step: int) -> float:
    """Warm-up (linear from 0) then cosine to a tenth of the peak."""
    peak, total = wl["lr"], wl["schedule_steps"]
    warm = total // 10 + 1
    if step < warm:
        return np.float32(peak) * np.float32(step) / np.float32(warm)
    t = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * (0.1 + 0.9 * 0.5 * (1 + np.cos(np.pi * t)))


def clip(grad: dict, max_norm: float = 1.0) -> dict:
    """Scale ``grad`` to global norm at most ``max_norm``."""
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in grad.values()))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return {k: g * scale for k, g in grad.items()}


@functools.partial(jax.jit, donate_argnums=(0,))
def clip_mean(grads: list[dict]) -> dict:
    """Each worker's gradient clipped to global norm 1, then their mean;
    the gradients' buffers are given up to it."""
    total = None
    for g in grads:
        g = clip(g)
        total = g if total is None else {k: total[k] + g[k] for k in g}
    return {k: v / len(grads) for k, v in total.items()}


def _adam_math(params, mu, nu, g, eta, t, b1, b2, eps):
    mu = {k: b1 * mu[k] + (1 - b1) * g[k] for k in g}
    nu = {k: b2 * nu[k] + (1 - b2) * g[k] * g[k] for k in g}
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = {k: params[k] - eta * ((mu[k] / c1)
                                    / (jnp.sqrt(nu[k] / c2) + eps))
              for k in g}
    return params, mu, nu


_adam = jax.jit(_adam_math, donate_argnums=(0, 1, 2))
#: for parameters that a worker's view still holds
_adam_keep = jax.jit(_adam_math, donate_argnums=(1, 2))


class Adam:
    """Adam (no weight decay) on a params dict, one step per push."""

    def __init__(self, wl: dict, params: dict):
        self.wl, self.t = wl, 0
        self.mu = {k: jnp.zeros_like(v) for k, v in params.items()}
        self.nu = {k: jnp.zeros_like(v) for k, v in params.items()}

    def step(self, params: dict, g: dict, keep: bool = False) -> dict:
        """The next parameters; ``keep`` leaves ``params`` intact, else
        its buffers are given up to the update."""
        eta = lr_at(self.wl, self.t)
        self.t += 1
        params, self.mu, self.nu = (_adam_keep if keep else _adam)(
            params, self.mu, self.nu, g, jnp.float32(eta),
            jnp.float32(self.t), self.wl["adam_b1"], self.wl["adam_b2"],
            self.wl["adam_eps"])
        return params


def leaf_sq(tree: dict) -> list[float]:
    """Per-leaf sums of squares, in sorted-path order."""
    return [float(jnp.sum(tree[k] * tree[k])) for k in sorted(tree)]


def leaf_sample(tree: dict) -> list[np.ndarray]:
    """Each leaf's entries at the comparison's fixed sample indices."""
    import compare
    return [np.asarray(_sample(tree[k]), np.float64) for k in sorted(tree)]


@jax.jit
def _sample(x):
    import compare
    return compare.take_sample(x)


# --------------------------------------------------------------------------- #
# the PSP virtual clock and barrier
# --------------------------------------------------------------------------- #
class Clock:
    """Per-worker virtual clock and barrier of a PSP cell (β ≥ W − 1)."""

    def __init__(self, wl: dict, open_barrier: bool = False):
        W = self.W = wl["workers"]
        self.wl = wl
        name = wl["barrier"]
        if name in ("pbsp", "pssp") and wl["sample_size"] < W - 1:
            raise ValueError("the reference samples every peer: "
                             "needs sample_size >= workers - 1")
        self.stale = {"bsp": 0, "pbsp": 0, "ssp": wl["staleness"],
                      "pssp": wl["staleness"], "asp": None}[name]
        if open_barrier:
            self.stale = None
        k_slow, k_dur, self.key = jax.random.split(
            jax.random.PRNGKey(wl["psp_seed"]), 3)
        n_slow = int(round(wl["straggler_frac"] * W))
        slow = jax.random.permutation(k_slow, jnp.arange(W) < n_slow)
        self.slow = np.asarray(slow)
        self.base = wl["base_compute"] * jnp.where(
            slow, jnp.float32(wl["straggler_slowdown"]), jnp.float32(1.0))
        self.busy = self._dur(k_dur)
        self.now = jnp.float32(0.0)
        self.step = np.zeros(W, np.int64)
        self.pushed = np.zeros(W, bool)

    def _dur(self, k):
        u = jax.random.uniform(k, (self.W,))
        return self.base * (1.0 + self.wl["compute_jitter"] * (u - 0.5))

    def pushes(self) -> np.ndarray:
        """Workers whose step completed and has not pushed yet."""
        return np.asarray(self.busy <= self.now) & ~self.pushed

    def advance(self, push: np.ndarray) -> np.ndarray:
        """Barrier, start and time advance after ``push``; who pulled."""
        self.key, _, k_dur = jax.random.split(self.key, 3)
        nxt = self._dur(k_dur)
        done = np.asarray(self.busy <= self.now)
        pushed = self.pushed | push
        lag = self.step[:, None] - self.step[None, :]
        if self.stale is None:
            ok = np.ones(self.W, bool)
        else:
            ok = np.all((lag <= self.stale) | np.eye(self.W, dtype=bool), 1)
        allowed = ok & done
        self.step = self.step + allowed
        busy = jnp.where(jnp.asarray(allowed), self.now + nxt, self.busy)
        self.pushed = np.where(allowed, False, pushed)
        blocked = done & ~allowed
        ahead = jnp.where(busy > self.now, busy, jnp.inf)
        t = jnp.min(ahead)
        if blocked.any():
            t = jnp.minimum(t, self.now + jnp.float32(self.wl["poll_interval"]))
        if bool(jnp.isfinite(t)):
            self.now = jnp.maximum(self.now, t)
        self.busy = busy
        return allowed


# --------------------------------------------------------------------------- #
# replay
# --------------------------------------------------------------------------- #
def replay(cfg: dict, wl: dict, seed: int, quant: str | None = None,
           fault: str | None = None) -> dict:
    """The first ``check_ticks`` ticks of a trainer cell from ``seed``.

    Returns per tick ``loss``, and for PSP cells ``steps``, ``pushed`` and
    ``pushes``; ``grad_sq``, the per-leaf sums of squares of the first
    gradient the optimizer got, and ``grad_sample``, its entries at the
    comparison's sample indices; ``delta_sq``, the per-leaf sums of
    squares of the change of the server parameters over the ticks.

    ``fault`` plants a fault, for reading what the comparison makes of it:
    ``half_batch`` leaves out the second half of each worker's rows and
    takes the mean over the rest; ``no_barrier`` lets every completed
    worker start its next step; ``straggler_push`` loses every push of a
    straggler (its gradient never reaches the server).
    """
    key = jax.random.PRNGKey(traffic.seed32(seed))
    params = init_params(cfg, key)
    opt = Adam(wl, params)
    block = wl["ref_block"]
    out = {"loss": [], "steps": [], "pushed": [], "pushes": [],
           "grad_sq": None}
    psp = wl["barrier"] != "none"
    W = wl["workers"] if psp else 1
    shape = ((W, wl["batch"], wl["seq"]) if psp else (wl["batch"], wl["seq"]))
    clock = Clock(wl, open_barrier=fault == "no_barrier") if psp else None
    views = [params] * W if psp else []
    for tick in range(wl["check_ticks"]):
        toks = traffic.tokens(seed, tick, shape, cfg["vocab_size"],
                              wl["tokens"])
        if fault == "half_batch":
            toks = toks[..., :max(1, toks.shape[-2] // 2), :]
        if not psp:
            loss, g = loss_and_grad(params, toks, cfg, block, quant)
            g = clip_mean([g])
            if out["grad_sq"] is None:
                out["grad_sq"] = leaf_sq(g)
                out["grad_sample"] = leaf_sample(g)
            params = opt.step(params, g)
            out["loss"].append(loss)
            out["pushes"].append(1)
            continue
        push = clock.pushes()
        if fault == "straggler_push":
            push &= ~clock.slow
        losses, grads = [], []
        for w in range(W):
            if push[w]:
                loss, g = loss_and_grad(views[w], toks[w], cfg, block, quant)
                grads.append(g)
            elif not push.any():    # a tick with no push reports every loss
                loss = loss_only(views[w], toks[w], cfg, block, quant)
            else:
                loss = None
            losses.append(loss)
        if push.any():
            g = clip_mean(grads)
            del grads
            if out["grad_sq"] is None:
                out["grad_sq"] = leaf_sq(g)
                out["grad_sample"] = leaf_sample(g)
            params = opt.step(params, g,
                              keep=any(v is params for v in views))
            del g
            out["loss"].append(float(np.mean([losses[w] for w in range(W)
                                              if push[w]])))
        else:
            out["loss"].append(float(np.mean(losses)))
        pulled = clock.advance(push)
        views = [params if pulled[w] else views[w] for w in range(W)]
        out["steps"].append(clock.step.tolist())
        out["pushed"].append(clock.pushed.tolist())
        out["pushes"].append(int(push.sum()))
    del views, opt
    p0 = init_params(cfg, key)
    out["delta_sq"] = leaf_sq({k: params[k] - p0[k] for k in params})
    return out
