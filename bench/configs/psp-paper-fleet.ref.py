"""Plain reference for the paper-scale PSP sweep: the same fleets, the same
noise, the paper's protocol stepped on a fixed grid.

Each scenario row is one parameter-server fleet (arXiv:1709.07772 §5):
``P`` nodes run SGD on a linear model ``y = X·w* + noise``; a node that
finishes a step pushes its minibatch gradient at the view it pulled, the
server applies ``w ← w − lr·Σ g / m`` for all of the tick's finishers, and
the node then asks its barrier whether it may start the next step.  The
barrier looks at every peer's step (BSP, SSP; DSSP with a staleness that
follows the observed spread; Elastic-BSP with a per-node credit from a
duration average), at a β-sample of peers (pBSP, pSSP; annealed β for
apbsp/apssp), or at nobody (ASP).  A node that may start pulls the server
model and draws its step's duration; one that may not waits (full view:
until the spread closes; sampled: it re-asks every poll interval).

The grid, the static draws and the random noise follow the sweep engine's
documented layout, so that the reference consumes the same numbers: per
row the ground truth and node speeds from ``numpy.random.default_rng(
seed)`` (normal truth over √d; speeds ``1 + jitter·(u − ½)``, a chosen
``round(frac·P)`` of them times the slowdown), the first finish times
from one SFC64 stream over all rows, and per grid tick the minibatches,
step-duration draws and peer scores from threefry keys folded from the
batch's seed (see :func:`_noise`).  Everything else is written here from
the protocol: the tick's phases, the barrier predicates, the sample (the
β smallest peer scores, self excluded), the adaptive updates and the
SGD step, in float32 with every contraction at ``Precision.HIGHEST``.

The noise layout is part of the semantics this benchmark holds the sweep
to, as the results themselves are: the keys folded per supertick and per
tick, the stride rule (:func:`_stride`: :data:`NOISE_BUDGET` and the
engine's :data:`ROW_BLOCK` row padding) and the shapes drawn.  A change to
the program that draws any number elsewhere reads not correct, even where
it means the same protocol.  Row padding alone moves nothing at the
paper's size: one tick's noise fills the budget, so the stride is 1 for
any padding (``tests/test_sweep.py`` pins it).

``precision="high"`` is the control: the data plane's contractions at
``Precision.HIGH``, which a TPU computes in three bfloat16 passes (a CPU
computes it in float32, so there it changes nothing).
``precision="bfloat16"`` rounds the contractions' operands to bfloat16
(one pass) by ``lax.reduce_precision``, which reads the same on any
backend; the CPU tests use it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: noise a supertick of the sweep engine may draw at once (bytes)
NOISE_BUDGET = 64 << 20
#: rows of the engine's data-plane block (its padded row count per device)
ROW_BLOCK = 16


# --------------------------------------------------------------------------- #
# the batch: static draws and the grid
# --------------------------------------------------------------------------- #
def rows(wl: dict) -> list[tuple[str, float]]:
    """(policy, straggler fraction) of each scenario row, in row order."""
    return [(p, f) for p in wl["policies"] for f in wl["straggler_fracs"]]


def batch(cfg: dict, wl: dict, seeds: list[int]) -> dict:
    """Static per-row arrays of one sweep whose rows have ``seeds``."""
    P, d = cfg["n_nodes"], cfg["dim"]
    R = rows(wl)
    B = len(R)
    out = {k: [] for k in ("w_true", "ct", "staleness", "beta", "asp",
                           "dssp", "lo", "ebsp", "adv", "alpha", "anneal",
                           "beta_lo")}
    for (name, frac), seed in zip(R, seeds):
        pol = cfg["policies"][name]
        rng = np.random.default_rng(seed)
        w_true = rng.normal(size=d) / np.sqrt(d)
        speed = 1.0 + cfg["compute_jitter"] * (rng.random(P) - 0.5)
        slow = rng.choice(P, size=int(round(frac * P)), replace=False)
        speed[slow] *= cfg["straggler_slowdown"]
        out["w_true"].append(w_true)
        out["ct"].append(cfg["base_compute"] * speed)
        out["staleness"].append(pol.get("staleness", 0))
        beta = pol.get("sample_size", -1) if name != "asp" else -1
        out["beta"].append(beta)
        out["asp"].append(name == "asp")
        out["dssp"].append(name == "dssp")
        out["lo"].append(pol.get("staleness_lo", 0))
        out["ebsp"].append(name == "ebsp")
        out["adv"].append(pol.get("max_advance", 0))
        out["alpha"].append(pol.get("ema_alpha", 0.5))
        out["anneal"].append(name in ("apbsp", "apssp"))
        out["beta_lo"].append(pol.get("sample_size_lo", 0))
    b = {k: np.asarray(v) for k, v in out.items()}
    b["sampled"] = b["beta"] >= 0
    b["full_view"] = ~b["sampled"] & ~b["asp"]
    b["beta_clip"] = np.clip(b["beta"], 0, P - 1)
    b["beta_lo"] = np.clip(b["beta_lo"], 0, b["beta_clip"])
    hops = max(1, math.ceil(math.log2(max(P, 2)))) + 1
    b["hops"] = np.where(cfg["distributed_sampling"] & b["sampled"], hops, 0)
    stream = np.random.Generator(np.random.SFC64(
        np.random.SeedSequence([int(s) for s in seeds] + [B, P, d])))
    first = b["ct"] * (0.5 + stream.random((B, P)))
    b["first"] = first.astype(np.float32)
    b["key_seed"] = int(np.random.SeedSequence(
        [int(s) for s in seeds] + [B, P, d]).generate_state(1)[0])
    dt = cfg["poll_interval"]
    ticks = np.arange(dt, cfg["duration"] + 1e-9, dt)
    if ticks[-1] < cfg["duration"] - 1e-9:
        ticks = np.append(ticks, cfg["duration"])
    m_times = np.arange(0.0, cfg["duration"] + 1e-9, cfg["measure_interval"])
    b["ticks"] = ticks
    b["measure"] = np.searchsorted(ticks, m_times[1:] - 1e-9)
    b["k_max"] = int(min(max(int(b["beta"].max()), 0), P - 1))
    b["stride"] = _stride(cfg, b, B)
    return b


def _stride(cfg: dict, b: dict, B: int) -> int:
    """Ticks per noise block: the largest divisor of every measurement
    tick's count (and of the grid) whose block fits the noise budget."""
    P, d, m = cfg["n_nodes"], cfg["dim"], cfg["batch"]
    b_pad = -(-B // ROW_BLOCK) * ROW_BLOCK
    words = P * m * (d + 1) + b_pad * P
    if b["k_max"] > 0:
        words += P if b["k_max"] == 1 else P * P
    q = int(np.gcd.reduce(np.concatenate(
        [b["measure"] + 1, [b["ticks"].size]]).astype(np.int64)))
    cap = max(1, NOISE_BUDGET // (4 * words))
    return max(s for s in range(1, q + 1) if q % s == 0 and s <= cap)


# --------------------------------------------------------------------------- #
# one grid tick
# --------------------------------------------------------------------------- #
def _noise(key, tick, stride, B, P, m, d, k_max):
    """The tick's minibatches, duration draws and peer scores."""
    sup, i = tick // stride, tick % stride
    k_sup = jax.random.fold_in(key, sup)
    k_mini, k_samp, k_dur, _ = jax.random.split(k_sup, 4)
    kt = jax.random.fold_in(k_mini, tick)
    blob = jax.vmap(lambda n: jax.random.normal(
        jax.random.fold_in(kt, n), (m, d + 1)))(jnp.arange(P))
    dur = jax.vmap(lambda r: jax.random.uniform(
        jax.random.fold_in(k_dur, r), (stride, P))[i])(jnp.arange(B))
    scores = None
    if k_max == 1:
        scores = jax.random.uniform(k_samp, (stride, P))[i]
    elif k_max > 1:
        scores = jax.vmap(lambda n: jax.random.uniform(
            jax.random.fold_in(k_samp, n), (stride, P))[i])(jnp.arange(P))
    return blob[..., :d], blob[..., d], dur, scores


def _sample_ok(steps, stal, beta_eff, scores, k_max):
    """(every sampled peer within staleness, peers sampled) per node."""
    B, P = steps.shape
    if k_max == 1:
        # one uniform over the P − 1 other nodes, shifted past self
        draw = jnp.floor(scores * (P - 1)).astype(jnp.int32)
        peer = draw + (draw >= jnp.arange(P))
        peers = peer[:, None]
    else:
        s = jnp.where(jnp.eye(P, dtype=bool), jnp.inf, scores)
        peers = jnp.argsort(s, axis=1, stable=True)[:, :k_max]
    used = jnp.arange(k_max)[None, None, :] < beta_eff[:, :, None]
    lag = steps[:, :, None] - steps[:, peers]
    ok = jnp.all((lag <= stal[:, :, None]) | ~used, axis=-1)
    return ok, jnp.sum(used, axis=-1)


def _contract(eq: str, a, b, precision: str):
    """``einsum`` in the given precision of the data plane."""
    if precision == "bfloat16":
        a, b = (lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
                for x in (a, b))
        precision = "highest"
    return jnp.einsum(eq, a, b, precision={
        "highest": lax.Precision.HIGHEST, "high": lax.Precision.HIGH}[precision])


def _tick(c, s, noise, t, cfg, b, prec):
    """One grid tick of every row: finishes, decisions, starts, SGD."""
    X, mb, u, scores = noise
    eps = jnp.float32(max(1e-9, 1e-3 * cfg["poll_interval"]))
    poll = jnp.float32(cfg["poll_interval"])
    active = t <= c["horizon"] + eps
    steps, busy, when, ready, blocked = (s["steps"], s["busy"], s["when"],
                                         s["ready"], s["blocked"])
    # finishes
    fin = busy & (when <= t + eps) & active[:, None]
    last = jnp.max(jnp.where(fin, when, -jnp.inf), axis=1)
    unblock = jnp.where(jnp.any(fin, axis=1), jnp.minimum(last, t), t)
    steps = steps + fin
    busy = busy & ~fin
    ready = jnp.where(fin, when, ready)
    blocked = blocked & ~fin
    # decisions
    cand = ~busy & (when <= t + eps) & active[:, None]
    mx, mn = jnp.max(steps, axis=1), jnp.min(steps, axis=1)
    slack = jnp.floor(c["adv"][:, None] * (1.0 - s["ema"] / jnp.maximum(
        jnp.max(s["ema"], axis=1, keepdims=True), 1e-9))).astype(jnp.int32)
    stal = jnp.where(c["dssp"], s["thr"], c["staleness"])[:, None]
    stal = jnp.where(c["ebsp"][:, None], slack, stal)
    beta_eff = jnp.where(c["anneal"], s["beta"], c["beta_clip"])[:, None]
    beta_eff = jnp.broadcast_to(beta_eff, steps.shape)
    full_ok = steps - mn[:, None] <= stal
    if b["k_max"] > 0:
        samp_ok, n_samp = _sample_ok(steps, stal, beta_eff, scores,
                                     b["k_max"])
    else:
        samp_ok = jnp.ones_like(fin)
        n_samp = jnp.zeros(steps.shape, jnp.int32)
    ok = jnp.where(c["asp"][:, None], True,
                   jnp.where(c["full_view"][:, None], full_ok, samp_ok))
    ctrl = jnp.sum(jnp.where(cand, n_samp * c["hops"][:, None], 0), axis=1)
    # starts, waits and polls
    start = cand & ok
    anchor = jnp.where(blocked & c["full_view"][:, None],
                       jnp.maximum(unblock[:, None], ready), ready)
    dur = c["ct"] * (1.0 + (u - 0.5))
    when = jnp.where(start, anchor + dur, when)
    busy = busy | start
    fail = cand & ~ok
    blocked = (blocked | fail) & ~start
    repoll = fail & c["sampled"][:, None]
    ready = jnp.where(repoll, ready + poll, ready)
    when = jnp.where(repoll, ready, when)
    # adaptive policies observe the tick
    gap = mx - mn
    thr = jnp.where(c["dssp"] & active,
                    jnp.clip(gap, c["lo"], c["staleness"]), s["thr"])
    beta = jnp.where(c["anneal"] & active,
                     jnp.clip(c["beta_lo"] + gap - c["staleness"],
                              c["beta_lo"], c["beta_clip"]), s["beta"])
    ema = jnp.where(c["ebsp"][:, None] & start,
                    (1.0 - c["alpha"][:, None]) * s["ema"]
                    + c["alpha"][:, None] * dur, s["ema"])
    # SGD: finishers push the gradient at their view, starters pull
    diff = s["view"] - c["w_true"][:, None, :]
    resid = (_contract("pmd,bpd->bpm", X, diff, prec)
             - c["noise"][:, None, None] * mb[None])
    resid = jnp.where(fin[:, :, None], resid, 0.0)
    g = _contract("bpm,pmd->bd", resid, X, prec) / X.shape[1]
    w = s["w"] - c["lr"][:, None] * g
    view = jnp.where(start[:, :, None], w[:, None, :], s["view"])
    new = dict(steps=steps, busy=busy, when=when, ready=ready,
               blocked=blocked, thr=thr, beta=beta, ema=ema, w=w, view=view,
               updates=s["updates"] + jnp.sum(fin, axis=1),
               control=s["control"] + ctrl)
    err = jnp.linalg.norm(w - c["w_true"], axis=1) / c["w_norm"]
    return new, err


def replay(cfg: dict, wl: dict, seeds: list[int],
           precision: str = "highest") -> dict:
    """One sweep of the cell's rows with ``seeds``; per row its final
    ``steps``, ``updates``, ``control`` and ``errors`` on the
    measurement grid (t = 0 included)."""
    if precision not in ("highest", "high", "bfloat16"):
        raise ValueError(f"unknown precision {precision!r}")
    prec = precision
    b = batch(cfg, wl, seeds)
    P, d, m = cfg["n_nodes"], cfg["dim"], cfg["batch"]
    B = len(seeds)
    f32 = jnp.float32
    w_true = b["w_true"].astype(np.float32)
    c = {"w_true": jnp.asarray(w_true),
         "w_norm": jnp.asarray(np.linalg.norm(b["w_true"], axis=1), f32),
         "ct": jnp.asarray(b["ct"], f32),
         "lr": jnp.full((B,), cfg["lr_times_nodes"] / P, f32),
         "noise": jnp.full((B,), cfg["noise_std"], f32),
         "horizon": jnp.full((B,), cfg["duration"], f32),
         "staleness": jnp.asarray(b["staleness"], jnp.int32),
         "beta_clip": jnp.asarray(b["beta_clip"], jnp.int32),
         "beta_lo": jnp.asarray(b["beta_lo"], jnp.int32),
         "lo": jnp.asarray(b["lo"], jnp.int32),
         "adv": jnp.asarray(b["adv"], f32),
         "alpha": jnp.asarray(b["alpha"], f32),
         "hops": jnp.asarray(b["hops"], jnp.int32)}
    for k in ("asp", "full_view", "sampled", "dssp", "ebsp", "anneal"):
        c[k] = jnp.asarray(b[k])
    s = {"steps": jnp.zeros((B, P), jnp.int32),
         "busy": jnp.ones((B, P), bool),
         "when": jnp.asarray(b["first"]), "ready": jnp.asarray(b["first"]),
         "blocked": jnp.zeros((B, P), bool),
         "thr": c["staleness"],
         "beta": jnp.where(c["anneal"], c["beta_lo"],
                           jnp.maximum(jnp.asarray(b["beta"]), 0)
                           ).astype(jnp.int32),
         "ema": jnp.zeros((B, P), f32),
         "w": jnp.zeros((B, d), f32), "view": jnp.zeros((B, P, d), f32),
         "updates": jnp.zeros((B,), jnp.int32),
         "control": jnp.zeros((B,), jnp.int32)}
    key = jax.random.PRNGKey(b["key_seed"])
    ticks = jnp.asarray(b["ticks"].astype(np.float32))

    @jax.jit
    def run(c, s):
        def body(s, i):
            noise = _noise(key, i, b["stride"], B, P, m, d, b["k_max"])
            return _tick(c, s, noise, ticks[i], cfg, b, prec)
        return lax.scan(body, s, jnp.arange(ticks.size))

    s, errs = jax.device_get(run(c, s))
    errs = np.asarray(errs, np.float64)[b["measure"]]
    return {"steps": np.asarray(s["steps"]),
            "updates": np.asarray(s["updates"]),
            "control": np.asarray(s["control"]),
            "errors": np.concatenate([np.ones((1, B)), errs]).T}
