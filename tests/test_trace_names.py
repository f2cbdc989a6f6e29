"""The names the program puts on its work for a profiler trace.

Device scopes (``jax.named_scope``) live in the compiled program's
``op_name`` metadata, which the profiler's trace carries for every device
op; host spans (``jax.profiler.TraceAnnotation``) land on the trace's host
plane.  The benchmark's trace reduction reads both by these exact names,
so a refactor that drops or renames one fails here.  Named scopes change
metadata only: the golden traces (``test_vector_sim_jax.py``,
``test_spmd_psp.py``) pin that no number moved.
"""
from __future__ import annotations

import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduced
from repro.core.barriers import make_barrier
from repro.core.simulator import SimConfig
from repro.core.spmd_psp import PSPConfig, psp_init
from repro.core.vector_sim import VectorSimulator, run_sweep
from repro.launch.steps import make_train_step
from repro.launch.train import make_psp_step
from repro.models import init_model
from repro.optim import adamw, warmup_cosine

SWEEP_SPANS = ("sweep.init", "sweep.stage", "sweep.dispatch", "sweep.fetch",
               "sweep.results")


def _scopes_named(hlo_text: str, names) -> set[str]:
    """The ``names`` that appear as a component of some op path (a
    transform wrapper such as ``vmap(...)`` around one counts)."""
    paths = re.findall(r'op_name="([^"]*)"', hlo_text)
    return {n for n in names for p in paths
            if re.search(rf"(^|[/(]){re.escape(n)}($|[/)])", p)}


def _psp_hlo() -> str:
    cfg = reduced(get_config("qwen2-0.5b"), n_layers=1, d_model=64)
    opt = adamw(warmup_cosine(3e-3, 1, 3))
    pcfg = PSPConfig(barrier="pbsp", n_workers=2, sample_size=1,
                     staleness=3, straggler_frac=0.5)
    state = jax.eval_shape(
        lambda k: psp_init(pcfg, init_model(cfg, k), opt.init, k),
        jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((2, 2, 16), jnp.int32)
    return make_psp_step(cfg, pcfg, opt).lower(state, toks).compile() \
        .as_text()


def _plain_hlo() -> str:
    cfg = reduced(get_config("qwen2-0.5b"), n_layers=1, d_model=64)
    opt = adamw(warmup_cosine(3e-3, 1, 3))
    params = jax.eval_shape(lambda k: init_model(cfg, k),
                            jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    return jax.jit(make_train_step(cfg, opt)).lower(
        params, jax.eval_shape(opt.init, params), batch).compile().as_text()


def _sweep_configs():
    return [SimConfig(n_nodes=16, duration=0.5, dim=8, batch=4, seed=s,
                      straggler_frac=0.25,
                      barrier=make_barrier(b, **kw))
            for s, (b, kw) in enumerate([("bsp", {}),
                                         ("pbsp", {"sample_size": 2})])]


def _sweep_hlo() -> str:
    from repro.core import vector_sim_jax
    sim = VectorSimulator(_sweep_configs(), backend="jax")
    chunk_fn, _, params, carry, xs = vector_sim_jax._prepare(sim)
    return chunk_fn.lower(params, carry, xs[0]).compile().as_text()


@pytest.mark.parametrize("build,names", [
    (_psp_hlo, ("train.grads", "train.optimizer", "train.barrier",
                "train.pull")),
    (_plain_hlo, ("train.grads", "train.optimizer")),
    (_sweep_hlo, ("sweep.noise", "sweep.data_plane")),
], ids=["psp_step", "plain_step", "sweep_chunk"])
def test_device_scopes_in_the_compiled_program(build, names, monkeypatch):
    monkeypatch.setenv("PSP_TICK_IMPL", "ref")
    assert _scopes_named(build(), names) == set(names)


def test_the_plain_step_has_no_psp_scopes():
    assert _scopes_named(_plain_hlo(),
                         ("train.barrier", "train.pull")) == set()


def test_sweep_host_spans_in_order(tmp_path, monkeypatch):
    """A jax-backend sweep under the profiler records its five host spans
    once each, in order, inside the caller's span (the benchmark's
    ``bench.sweep``)."""
    monkeypatch.setenv("PSP_TICK_IMPL", "ref")
    configs = _sweep_configs()
    run_sweep(configs, backend="jax")            # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.sweep"):
            run_sweep(configs, backend="jax")
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    from jax.profiler import ProfileData
    spans = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for pl in ProfileData.from_file(path).planes
                   if pl.name.startswith("/host:")
                   for line in pl.lines for e in line.events
                   if e.name in SWEEP_SPANS + ("bench.sweep",))
    caller = [s for s in spans if s[2] == "bench.sweep"]
    ours = [s for s in spans if s[2] != "bench.sweep"]
    assert len(caller) == 1
    assert [n for _, _, n in ours] == list(SWEEP_SPANS)
    assert all(a[1] <= b[0] for a, b in zip(ours, ours[1:]))
    lo, hi, _ = caller[0]
    assert lo <= ours[0][0] and ours[-1][1] <= hi

