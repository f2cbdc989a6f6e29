"""Serving launcher: one-shot batched decode, or a live hot-swapping server.

One-shot (legacy wave mode)::

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-780m --reduced \
        --requests 16 --max-new 32 --top-k 50 --temperature 0.8

Live mode — watch a snapshot directory a trainer publishes into
(``repro.launch.train --publish-dir``) and hot-swap params mid-traffic::

    PYTHONPATH=src python -m repro.launch.serve --reduced \
        --watch-dir /tmp/snaps --requests 32

In live mode requests flow through the :class:`InferenceServer` admission
queue and every completion reports the snapshot version it was decoded
on; in-flight requests are never disturbed by a swap.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, reduced as make_reduced
from repro.core.compile_cache import enable_compile_cache
from repro.models import init_model
from repro.serving import (InferenceServer, Request, ServeConfig,
                           ServingEngine, SnapshotWatcher)


def _serve_live(a, cfg, params, scfg) -> int:
    watcher = SnapshotWatcher(a.watch_dir, params)
    loaded = watcher.poll()
    version = 0
    if loaded is not None:
        params, version = loaded
        print(f"loaded snapshot v{version} from {a.watch_dir}")
    eng = ServingEngine(params, cfg, scfg, version=version)
    rng = np.random.default_rng(a.seed)
    t0 = time.time()
    with InferenceServer(eng, watcher=watcher,
                         poll_every=a.poll_every) as srv:
        futs = [srv.submit(Request(prompt=rng.integers(
            0, cfg.vocab_size, size=a.prompt_len).astype(np.int32)))
            for _ in range(a.requests)]
        comps = [f.result(timeout=a.timeout) for f in futs]
    dt = time.time() - t0
    total_new = sum(len(c.tokens) for c in comps)
    versions = sorted({c.snapshot_version for c in comps})
    st = srv.stats
    print(f"arch={cfg.name} requests={a.requests} new_tokens={total_new} "
          f"wall={dt:.2f}s ({total_new / dt:.1f} tok/s) "
          f"swaps={st.swaps} versions={versions}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=512,
                    help="per-group cache capacity")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=None,
                    help="top-k sampling cutoff (with --temperature > 0)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop decoding a request at this token id")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--watch-dir", default=None,
                    help="serve live: hot-swap snapshots published here")
    ap.add_argument("--poll-every", type=int, default=8,
                    help="live mode: poll --watch-dir every N decode ticks")
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="live mode: per-request completion timeout (s)")
    a = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(a.arch)
    if a.reduced:
        cfg = make_reduced(cfg)
    params = init_model(cfg, jax.random.PRNGKey(a.seed))
    scfg = ServeConfig(batch=a.batch, max_len=a.max_len,
                       max_new_tokens=a.max_new, temperature=a.temperature,
                       top_k=a.top_k, eos_id=a.eos_id, seed=a.seed)
    if a.watch_dir:
        return _serve_live(a, cfg, params, scfg)

    eng = ServingEngine(params, cfg, scfg)
    rng = np.random.default_rng(a.seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=a.prompt_len)
               .astype(np.int32) for _ in range(a.requests)]
    t0 = time.time()
    outs = eng.generate(prompts)
    dt = time.time() - t0
    total_new = sum(len(o) for o in outs)
    print(f"arch={cfg.name} requests={a.requests} new_tokens={total_new} "
          f"wall={dt:.2f}s ({total_new/dt:.1f} tok/s)")
    for i, o in enumerate(outs[:4]):
        print(f"  req{i}: {o[:12].tolist()}...")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
