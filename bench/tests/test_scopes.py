"""The scope reduction (``scopes.py``): the XPlane decoder on the trace
recorded on a TPU v5e (``data/probe.xplane.pb``), the scope classifier on
the compiled programs of the cells' paths, and the reduction, the idle
attribution and the readers on a trace written here."""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
import tiny

trace = run.module("trace.py")
scopes = run.module("scopes.py")
PROBE = os.path.join(os.path.dirname(__file__), "data", "probe.xplane.pb")


def read(metric, tr):
    return run.module("metrics", f"{metric}.py").read({"trace": tr})


# ---------------------------------------------------------------------- #
# the decoder, on the probe


def test_decoder_reads_the_op_path():
    _, devices = scopes.planes(PROBE)
    paths = {n.split(" = ")[0]: p for n, _, _, p in devices["/device:TPU:0"]}
    assert paths["%broadcast_add_fusion"] == "jit(add)/add:"
    assert paths["%convolution_tanh_fusion"] == "jit(<lambda>)/dot_general:"
    assert paths["%copy-done"] == ""


def test_decoder_intervals_are_profile_datas():
    spans, devices = trace.planes(PROBE)
    ours, our_devices = scopes.planes(PROBE)
    assert sorted(ours) == sorted(spans)
    assert list(our_devices) == list(devices)
    for dev, evs in devices.items():
        assert [(n, s, e) for n, s, e, _ in our_devices[dev]] == evs


def test_trace_reduce_on_the_probe_is_unchanged():
    r = trace.reduce(PROBE, 1)
    assert r["busy_s"] == 0.000118227
    assert r["window_s"] == 0.255545567
    assert r["idle"] == {"bench.step": 0.244604012,
                         "bench.data": 0.008615319,
                         "bench.read": 0.002208009}
    assert [op.split(" = ")[0] for op, _ in
            r["breakdown"]["device_ops"]][:3] == [
        "%fusion", "%convolution_tanh_fusion", "%broadcast_add_fusion"]
    assert len(r["ops"]) == 6


def test_scope_reduction_of_the_probe():
    r, s = trace.reduce(PROBE, 1), scopes.reduce(PROBE, 1)
    assert s["busy_s"] == r["busy_s"] and s["window_s"] == r["window_s"]
    # nothing in the probe is scoped: every op is unscoped; no op frames
    # another, so the busy time is the leaf ops' sum
    assert set(s["scopes"]) == {scopes.UNSCOPED}
    assert s["scopes"][scopes.UNSCOPED] == pytest.approx(
        sum(r["ops"].values()), rel=1e-12)
    assert s["scopes"][scopes.UNSCOPED] == pytest.approx(r["busy_s"],
                                                         rel=1e-12)
    # with no program span, the idle gaps go where trace.py puts them
    assert s["idle"] == pytest.approx(r["idle"], rel=1e-12)
    assert s["host"] == {}
    assert s["span_counts"] == {"bench.data": 3, "bench.step": 3,
                                "bench.read": 3}


# ---------------------------------------------------------------------- #
# the classifier


@pytest.mark.parametrize("path,scope", [
    ("jit(step)/train.optimizer/sub", "train.optimizer"),
    ("jit(<lambda>)/vmap(train.grads)/transpose(jvp())/dot_general:",
     "train.grads"),
    ("jit(f)/transpose(jvp(train.grads))/mul", "train.grads"),
    ("jit(sharded)/shard_map/while/body/closed_call/sweep.noise/"
     "jit(_normal)/jit(_uniform)/xor", "sweep.noise"),
    ("jit(f)/train.barrier/train.pull/select_n", "train.pull"),
    ("jit(f)/train.grads_x/add", None),
    ("jit(add)/add:", None),
    ("", None),
])
def test_scope_of(path, scope):
    assert scopes.scope_of(path) == scope


def test_bucket_names_a_kernel_outside_every_scope():
    tick = "%psp_tick.8 = (s32[16,1000]) custom-call(%a)"
    assert scopes.bucket(tick, "jit(s)/while/body/pallas_call",
                         ("psp_tick",)) == "psp_tick"
    assert scopes.bucket(tick, "jit(s)/sweep.noise/x",
                         ("psp_tick",)) == "sweep.noise"
    assert scopes.bucket("%copy.125 = f32[8]", "", ("psp_tick",)) \
        == scopes.UNSCOPED


def _fusion_paths(hlo_text: str) -> list[str]:
    """The op path of each fusion the compiled program runs ('' where the
    fusion carries none)."""
    out = []
    for line in hlo_text.splitlines():
        if " fusion(" in line and "calls=" in line:
            m = re.search(r'op_name="([^"]*)"', line)
            out.append(m.group(1) if m else "")
    return out


def _psp_step():
    from repro.core.spmd_psp import PSPConfig, psp_init
    from repro.launch.train import make_psp_step
    from repro.models import init_model
    drv = run.module("drivers", "train.py")
    wl, cfg = tiny.cell("train.qwen2-0.5b.pbsp")
    mc, opt = drv.model_config(cfg), drv.optimizer(wl)
    pcfg = PSPConfig(barrier=wl["barrier"], n_workers=wl["workers"],
                     sample_size=wl["sample_size"],
                     staleness=wl["staleness"],
                     straggler_frac=wl["straggler_frac"])
    state = jax.eval_shape(
        lambda k: psp_init(pcfg, init_model(mc, k), opt.init, k),
        jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((wl["workers"], wl["batch"], wl["seq"]),
                                jnp.int32)
    return make_psp_step(mc, pcfg, opt).lower(state, toks)


def _plain_step():
    from repro.launch.steps import make_train_step
    from repro.models import init_model
    drv = run.module("drivers", "train.py")
    wl, cfg = tiny.cell("train.qwen2-0.5b.sync")
    mc, opt = drv.model_config(cfg), drv.optimizer(wl)
    params = jax.eval_shape(lambda k: init_model(mc, k),
                            jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((wl["batch"], wl["seq"]), jnp.int32)
    return jax.jit(make_train_step(mc, opt)).lower(
        params, jax.eval_shape(opt.init, params), {"tokens": toks})


def _sweep_chunk():
    from repro.core import vector_sim_jax
    from repro.core.vector_sim import VectorSimulator
    drv = run.module("drivers", "sweep.py")
    wl, cfg = run.cell("sweep.paper-fleet.nine")
    cfg = dict(cfg, n_nodes=64, dim=16, duration=1.0)
    sim = VectorSimulator(drv.sim_configs(cfg, wl, drv.row_seeds(wl, 7, 0)),
                          backend="jax")
    chunk_fn, _, params, carry, xs = vector_sim_jax._prepare(sim)
    return chunk_fn.lower(params, carry, xs[0])


@pytest.mark.parametrize("lower,names,least", [
    (_psp_step, ("train.grads", "train.optimizer", "train.barrier",
                 "train.pull"), 0.8),
    (_plain_step, ("train.grads", "train.optimizer"), 0.8),
    # the jnp tick's control plane (the Pallas kernel's on the chip) is
    # in no scope
    (_sweep_chunk, ("sweep.noise", "sweep.data_plane"), 0.5),
], ids=["psp_step", "plain_step", "sweep_chunk"])
def test_classifier_finds_every_scope(lower, names, least, monkeypatch):
    monkeypatch.setenv("PSP_TICK_IMPL", "ref")
    text = lower().compile().as_text()
    found = {scopes.scope_of(p)
             for p in re.findall(r'op_name="([^"]*)"', text)}
    assert found - {None} == set(names)
    fusions = [scopes.scope_of(p) for p in _fusion_paths(text)]
    assert sum(f is not None for f in fusions) >= least * len(fusions)


# ---------------------------------------------------------------------- #
# the reduction, on a trace written here


def _write(path, ops, spans, ref_paths=()):
    """An ``.xplane.pb`` with one device's ops ``(name, start, end, op
    path)`` and host spans ``(name, start, end)``, in nanoseconds; the op
    paths in ``ref_paths`` are stored as references to stat metadata."""
    sp = scopes._xspace_class()()
    dev = sp.planes.add(name=b"/device:TPU:0")
    tf_op = dev.stat_metadata.add(key=1)
    tf_op.value.id, tf_op.value.name = 1, b"tf_op"
    line = dev.lines.add(name=b"XLA Ops", timestamp_ns=5)
    for i, (name, s, e, p) in enumerate(ops, 2):
        md = dev.event_metadata.add(key=i)
        md.value.id, md.value.name = i, name.encode()
        if p in ref_paths:
            ref = dev.stat_metadata.add(key=100 + i)
            ref.value.id, ref.value.name = 100 + i, p.encode()
            md.value.stats.add(metadata_id=1, ref_value=100 + i)
        elif p:
            md.value.stats.add(metadata_id=1, str_value=p.encode())
        line.events.add(metadata_id=i, offset_ps=(s - 5) * 1000 + 250,
                        duration_ps=(e - s) * 1000 + 500)
    host = sp.planes.add(name=b"/host:CPU")
    hl = host.lines.add(name=b"python3", timestamp_ns=0)
    for i, (name, s, e) in enumerate(spans, 1):
        md = host.event_metadata.add(key=i)
        md.value.id, md.value.name = i, name.encode()
        hl.events.add(metadata_id=i, offset_ps=s * 1000,
                      duration_ps=(e - s) * 1000)
    with open(path, "wb") as f:
        f.write(sp.SerializeToString())


OPS = [("%fusion.1 = f32[]", 10, 30, "jit(s)/vmap(train.grads)/jvp()/dot"),
       ("%fusion.2 = f32[]", 30, 40, "jit(s)/train.optimizer/sub"),
       ("%psp_tick.3 = s32[]", 40, 45, "jit(s)/while/body/pallas_call"),
       ("%copy.4 = f32[]", 45, 50, ""),
       ("%while.5 = f32[]", 110, 152, "jit(s)/while"),
       ("%fusion.6 = f32[]", 110, 130, "jit(s)/while/body/sweep.noise/x"),
       ("%fusion.7 = f32[]", 130, 150,
        "jit(s)/while/body/sweep.data_plane/y"),
       ("%fusion.8 = f32[]", 190, 260, "jit(s)/train.pull/select")]
SPANS = [("bench.step", 0, 100), ("bench.step", 100, 200),
         ("sweep.stage", 60, 70), ("sweep.fetch", 150, 180),
         ("unrelated", 0, 300)]


def test_reduce_partitions_the_busy_time(tmp_path):
    path = str(tmp_path / "t.xplane.pb")
    _write(path, OPS, SPANS, ref_paths=(OPS[5][3],))
    r = scopes.reduce(path, 1, kernels=("psp_tick",))
    ns = 1e-9
    assert r["window_s"] == pytest.approx(200 * ns)
    assert r["busy_s"] == pytest.approx(92 * ns)
    assert r["scopes"] == pytest.approx({
        "train.grads": 20 * ns, "train.optimizer": 10 * ns,
        "psp_tick": 5 * ns, scopes.UNSCOPED: 7 * ns,
        "sweep.noise": 20 * ns, "sweep.data_plane": 20 * ns,
        "train.pull": 10 * ns})          # clipped at the window's end
    assert sum(r["scopes"].values()) == pytest.approx(r["busy_s"])
    # the gaps [0, 10), [50, 110) and [152, 190) all lie in bench.step
    # spans; a program span overlaps the second (sweep.stage) and the
    # third (sweep.fetch), and takes the whole gap
    assert r["idle"] == pytest.approx({"bench.step": 10 * ns,
                                       "sweep.stage": 60 * ns,
                                       "sweep.fetch": 38 * ns})
    assert r["host"] == {"sweep.stage": {"s": pytest.approx(10 * ns),
                                         "n": 1},
                         "sweep.fetch": {"s": pytest.approx(30 * ns),
                                         "n": 1}}
    assert r["span_counts"] == {"bench.step": 2}
    assert r["top_ops"]["train.grads"] == [["%fusion.1 = f32[]",
                                            pytest.approx(20 * ns)]]
    assert "trace: idle" in scopes.idle_line(r)
    assert "sweep.stage" in scopes.idle_line(r)


def test_innermost_gives_a_frame_its_uncovered_time():
    evs = [("while", 0, 100, ""), ("a", 10, 30, ""), ("b", 30, 60, ""),
           ("c", 90, 120, ""), ("d", 95, 130, "")]    # c and d overlap
    assert scopes.innermost(evs, 0, 200) == {0: 40, 1: 20, 2: 30, 3: 5,
                                             4: 35}
    # clipped to the window; an event wholly outside it gets nothing
    assert scopes.innermost(evs, 65, 97) == {0: 25, 3: 5, 4: 2}


@pytest.mark.parametrize("seed", range(5))
def test_innermost_sums_to_the_busy_union(seed):
    rng = np.random.default_rng(seed)
    starts = rng.integers(0, 1000, 60)
    evs = [(str(i), int(s), int(s + rng.integers(1, 200)), "")
           for i, s in enumerate(starts)]
    lo, hi = 100, 900
    busy = trace.union(trace.clip([(s, e) for _, s, e, _ in evs], lo, hi))
    got = scopes.innermost(evs, lo, hi)
    assert sum(got.values()) == sum(e - s for s, e in busy)
    assert all(v > 0 for v in got.values())


def test_attribute_prefers_the_program_span():
    gaps = [(0, 10), (20, 30), (40, 50)]
    program = [("sweep.fetch", 25, 45)]
    harness = [("bench.sweep", 0, 35)]
    assert scopes.attribute(gaps, program, harness) == {
        "bench.sweep": 10, "sweep.fetch": 20}
    assert scopes.attribute([(60, 61)], program, harness) == {"other": 1}


def test_readers(tmp_path):
    path = str(tmp_path / "t.xplane.pb")
    _write(path, OPS, SPANS)
    r = scopes.reduce(path, 1, kernels=("psp_tick",))
    tr = dict(r, counts={"ticks": 4})
    ns = 1e-9
    assert read("grads_ms.train", tr) == pytest.approx(1e3 * 20 * ns / 2)
    assert read("optimizer_ms.train", tr) == pytest.approx(1e3 * 10 * ns / 2)
    assert read("psp_ms.train", tr) == pytest.approx(1e3 * 10 * ns / 2)
    assert read("noise_us.sweep", tr) == pytest.approx(1e6 * 20 * ns / 4)
    assert read("data_plane_us.sweep", tr) == pytest.approx(1e6 * 20 * ns / 4)
    # no bench.sweep span: no sweep to divide by
    assert read("sweep_host_ms.sweep", tr) is None
    sweep = {"host": {"sweep.init": {"s": 0.01, "n": 2},
                      "sweep.stage": {"s": 0.02, "n": 2},
                      "sweep.fetch": {"s": 5.0, "n": 2},
                      "sweep.results": {"s": 0.03, "n": 2}},
             "span_counts": {"bench.sweep": 2}}
    assert read("sweep_host_ms.sweep", sweep) == pytest.approx(30.0)


def test_readers_find_nothing_in_an_unscoped_trace():
    tr = trace.reduce(PROBE, 1)
    for m in scopes.METRICS:
        assert read(m, tr) is None, m
    tr = dict(tr, **{k: v for k, v in scopes.reduce(PROBE, 1).items()
                     if k in ("scopes", "host", "span_counts")})
    for m in scopes.METRICS:
        assert read(m, tr) is None, m
