"""Training launcher.

Two modes:

* ``--barrier none``  — plain synchronous (pjit) training: the classical
  data+tensor-parallel path used by the dry-run.
* ``--barrier {bsp,ssp,asp,pbsp,pssp}`` — PSP training (the paper's
  technique as a first-class feature): W worker views, seeded virtual-clock
  heterogeneity, masked server aggregation (core/spmd_psp.py).

Fault tolerance: with ``--ckpt-dir`` the run cuts *full-state* checkpoints
through the async :class:`repro.checkpoint.CheckpointManager` — every
``--save-every`` steps and/or ``--save-interval`` wall-clock seconds, plus
one at the final step.  The PSP mode persists the entire
:class:`~repro.core.spmd_psp.PSPState` (server params, optimizer state,
worker views, step/busy/pushed/alive arrays, churn cursors, policy pytree,
RNG key), the pjit mode persists ``{params, opt_state}``.  ``--resume``
restores the newest checkpoint and fast-forwards the synthetic data
stream to the restored step, so a SIGKILL'd run resumed with the same
flags reproduces the uninterrupted run bit-for-bit
(``tests/test_checkpoint.py`` pins this with a real subprocess kill).

Live serving: ``--publish-dir`` additionally publishes *serving
snapshots* (params only — ``server_params`` in PSP mode) every
``--publish-every`` steps over the trainer→server snapshot bus
(:mod:`repro.serving.snapshot_bus`), plus one final snapshot; a live
server (``repro.launch.serve --watch-dir``) hot-swaps them mid-traffic.

CPU example (used by examples/train_e2e.py):

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \
        --steps 200 --batch 8 --seq 128 --barrier pbsp --workers 4 \
        --ckpt-dir /tmp/ck --save-every 50
    # ... SIGKILL mid-run, then:
    PYTHONPATH=src python -m repro.launch.train ... --ckpt-dir /tmp/ck --resume
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (CheckpointManager, CheckpointPolicy,
                              latest_step, restore_checkpoint)
from repro.configs import get_config, reduced as make_reduced
from repro.core.compile_cache import enable_compile_cache
from repro.core.spmd_psp import (PSPConfig, psp_init, psp_train_step,
                                 state_from_tree, state_to_tree)
from repro.data import SyntheticLM
from repro.launch.steps import make_train_step
from repro.models import init_model, loss_fn
from repro.optim import adamw, clip_by_norm, warmup_cosine
from repro.serving.snapshot_bus import SnapshotPublisher


def _make_manager(a) -> CheckpointManager | None:
    """The run's async checkpointer (None when ``--ckpt-dir`` is unset)."""
    if not a.ckpt_dir:
        return None
    return CheckpointManager(
        a.ckpt_dir,
        CheckpointPolicy(every_steps=a.save_every or None,
                         every_seconds=a.save_interval or None),
        keep=a.keep)


def _maybe_resume(a, template):
    """Restore the newest checkpoint into ``template`` if ``--resume``.

    Returns ``(tree, start_step)`` — the template itself and 0 when there
    is nothing to resume from (first launch with ``--resume`` is legal:
    the flag means "continue if a checkpoint exists", so crash-loop
    supervisors can pass it unconditionally).
    """
    if not (a.resume and a.ckpt_dir) or latest_step(a.ckpt_dir) is None:
        return template, 0
    tree, step = restore_checkpoint(a.ckpt_dir, template)
    print(f"resumed step {step} from {a.ckpt_dir}")
    return tree, step


def make_psp_step(cfg, pcfg: PSPConfig, opt):
    """The jitted PSP tick ``(state, tokens[W, b, s]) -> (state, metrics)``
    for model ``cfg`` under barrier config ``pcfg``, with the carried
    ``PSPState`` donated.  Each worker's forward and backward run under
    the device scope ``train.grads``;
    :func:`~repro.core.spmd_psp.psp_apply_tick` scopes the rest."""
    def grad_fn(p, tokens):
        with jax.named_scope("train.grads"):
            (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                p, {"tokens": tokens}, cfg)
            return loss, clip_by_norm(g, 1.0)

    return jax.jit(lambda s, b: psp_train_step(pcfg, grad_fn, opt.update,
                                               s, b), donate_argnums=(0,))


def _fingerprint(params) -> np.ndarray:
    """Per-leaf f32 sums of squares on the host: a few bytes that tell
    whether a step moved the params, without a second device copy."""
    return np.asarray(jax.device_get(
        [jnp.sum(jnp.square(x.astype(jnp.float32)))
         for x in jax.tree.leaves(params)]))


def main(argv=None) -> int:
    """Command-line entry point: :func:`run`, exit code 0."""
    run(argv)
    return 0


def run(argv=None) -> dict:
    """Train with the command-line flags in ``argv``.

    Each step's jitted function donates the carried training state (pjit:
    params and optimizer state; PSP: the whole ``PSPState``), so XLA
    updates it in place instead of holding a second copy.  Whatever reads
    the state after a step — checkpoints, serving snapshots — takes its
    host copy synchronously, before the next step consumes the buffers.

    Returns a report: per-step ``loss`` and ``pushes`` (landed gradient
    pushes; the global batch count in pjit mode), and per-leaf param
    fingerprints ``params_before``/``params_after`` (:func:`_fingerprint`).
    """
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--n-layers", type=int, default=2)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--barrier", default="none",
                    choices=["none", "bsp", "ssp", "asp", "pbsp", "pssp"])
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--sample-size", type=int, default=2)
    ap.add_argument("--staleness", type=int, default=3)
    ap.add_argument("--straggler-frac", type=float, default=0.25)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=0,
                    help="checkpoint every N steps (0: final step only)")
    ap.add_argument("--save-interval", type=float, default=0.0,
                    help="checkpoint every T wall-clock seconds (0: off)")
    ap.add_argument("--keep", type=int, default=3,
                    help="checkpoints retained by GC (older are deleted)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest checkpoint in --ckpt-dir "
                         "(no-op when none exists) and continue")
    ap.add_argument("--throttle", type=float, default=0.0,
                    help="sleep per step; paces the run so kill-and-resume "
                         "tests get a deterministic mid-run kill window")
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--publish-dir", default=None,
                    help="publish serving snapshots (params only) here "
                         "for a live server (repro.launch.serve "
                         "--watch-dir) to hot-swap")
    ap.add_argument("--publish-every", type=int, default=25,
                    help="snapshot-publication step cadence")
    a = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(a.arch)
    if a.reduced:
        cfg = make_reduced(cfg, n_layers=a.n_layers, d_model=a.d_model)
        cfg = dataclasses.replace(cfg, vocab_size=a.vocab)
    opt = adamw(warmup_cosine(a.lr, a.steps // 10 + 1, a.steps))
    key = jax.random.PRNGKey(a.seed)
    params = init_model(cfg, key)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params:,} barrier={a.barrier}")
    params_before = _fingerprint(params)
    losses, pushes = [], []

    mgr = _make_manager(a)
    pub = (SnapshotPublisher(a.publish_dir, every_steps=a.publish_every)
           if a.publish_dir else None)
    meta = {"arch": cfg.name, "barrier": a.barrier}
    t0 = time.time()
    if a.barrier == "none":
        data = iter(SyntheticLM(cfg.vocab_size, a.seq, a.batch, seed=a.seed))
        state = opt.init(params)
        tree, start = _maybe_resume(a, {"params": params,
                                        "opt_state": state})
        params, state = tree["params"], tree["opt_state"]
        for _ in range(start):       # replay the consumed data stream
            next(data)
        step_fn = jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1))
        for t in range(start, a.steps):
            batch = next(data)
            params, state, loss, _ = step_fn(params, state, batch)
            losses.append(loss)
            pushes.append(1)
            if t % a.log_every == 0 or t == a.steps - 1:
                print(f"step {t:5d} loss {float(loss):.4f} "
                      f"({time.time()-t0:.1f}s)")
            if mgr:
                mgr.maybe_save(t + 1, {"params": params, "opt_state": state},
                               {**meta, "data_step": t + 1})
            if pub:
                pub.maybe_publish(t + 1, params, meta)
            if a.throttle:
                time.sleep(a.throttle)
        final_tree = {"params": params, "opt_state": state}
    else:
        W = a.workers
        data = iter(SyntheticLM(cfg.vocab_size, a.seq, W * a.batch,
                                seed=a.seed))
        pcfg = PSPConfig(barrier=a.barrier, n_workers=W,
                         sample_size=a.sample_size, staleness=a.staleness,
                         straggler_frac=a.straggler_frac)

        st = psp_init(pcfg, params, opt.init, jax.random.fold_in(key, 1))
        tree, start = _maybe_resume(a, state_to_tree(st))
        st = state_from_tree(tree)
        for _ in range(start):       # replay the consumed data stream
            next(data)
        step_fn = make_psp_step(cfg, pcfg, opt)
        del params, tree             # st owns the buffers from here on
        for t in range(start, a.steps):
            toks = next(data)["tokens"].reshape(W, a.batch, a.seq)
            st, m = step_fn(st, toks)
            losses.append(m["loss"])
            pushes.append(m["pushes"])
            if t % a.log_every == 0 or t == a.steps - 1:
                print(f"tick {t:5d} loss {float(m['loss']):.4f} "
                      f"vtime {float(m['virtual_time']):.2f}s "
                      f"mean_step {float(m['mean_step']):.1f} "
                      f"spread {int(m['step_spread'])} "
                      f"({time.time()-t0:.1f}s)")
            if mgr:
                mgr.maybe_save(t + 1, state_to_tree(st),
                               {**meta, "data_step": t + 1})
            if pub:
                pub.maybe_publish(t + 1, st.server_params, meta)
            if a.throttle:
                time.sleep(a.throttle)
        params = st.server_params
        final_tree = state_to_tree(st)
    if mgr:
        if a.steps > start:
            mgr.save(a.steps, final_tree, {**meta, "data_step": a.steps},
                     block=True)
        mgr.close()
        print(f"checkpoint: step {mgr.latest_step()} in {a.ckpt_dir}")
    if pub:
        if a.steps > start:
            pub.publish(a.steps, params, meta, block=True)
        pub.close()
        print(f"published {pub.published} snapshots to {a.publish_dir}")
    return {"loss": [float(x) for x in jax.device_get(losses)],
            "pushes": [int(x) for x in jax.device_get(pushes)],
            "params_before": params_before,
            "params_after": _fingerprint(params)}


if __name__ == "__main__":
    raise SystemExit(main())
