import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: prove every (arch × input-shape × mesh) combination
lowers, SPMD-partitions and compiles on the production mesh — and extract
the memory/cost/collective artifacts the roofline analysis consumes.

MUST be imported/run before anything else initialises jax (the device count
is locked at first backend init) — hence the XLA_FLAGS lines above all other
imports.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun \
        --arch gemma2-27b --shape train_4k --mesh single \
        --out results/dryrun
    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
        --mesh single,multi

Each combo writes ``results/dryrun/<arch>__<shape>__<mesh>.json`` with
memory_analysis, cost_analysis, parsed collective bytes and wall times —
idempotent (skips existing files unless --force).
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, INPUT_SHAPES, LONG_CONTEXT_ARCHS, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import dryrun_inputs
from repro.parallel.sharding import make_rules, psp_worker_axes, use_rules
from repro.roofline.analysis import (HW, collective_bytes, model_flops,
                                     roofline_report)
from repro.roofline.hlo_cost import analyze_hlo


def should_skip(arch: str, shape_name: str) -> bool:
    return shape_name == "long_500k" and arch not in LONG_CONTEXT_ARCHS


def run_psp_combo(arch: str, mesh_kind: str, out_dir: str,
                  workers: int = 0, force: bool = False) -> dict:
    """Lower + compile the PSP train step (the paper's technique as the
    trainer) on the production mesh — §Perf pair 3 artifact."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.spmd_psp import PSPConfig, PSPState
    from repro.launch.steps import abstract_opt_state, make_psp_train_step
    from repro.models import model_defs
    from repro.models.params import ParamDef, abstract_params
    from repro.optim import adamw

    tag = f"{arch}__train_4k_psp__{mesh_kind}"
    path = os.path.join(out_dir, tag + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    cfg = get_config(arch)
    shape = INPUT_SHAPES["train_4k"]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.devices.size
    rules = make_rules(cfg, shape, mesh)
    rules.table["psp_workers"] = psp_worker_axes(mesh)
    # default: one PSP worker per (pod × data) shard group
    W = workers or (32 if mesh_kind == "multi" else 16)
    rec = {"arch": arch, "shape": "train_4k_psp", "mesh": mesh_kind,
           "chips": int(chips), "workers": W, "status": "error"}
    t0 = time.time()
    try:
        defs = model_defs(cfg)
        aparams = abstract_params(defs, jnp.float32, rules)

        def stack(d):
            return jax.tree.map(
                lambda pd: ParamDef((W,) + pd.shape,
                                    ("psp_workers",) + pd.axes,
                                    init=pd.init, scale=pd.scale,
                                    dtype=pd.dtype),
                d, is_leaf=lambda x: isinstance(x, ParamDef))

        aviews = abstract_params(stack(defs), jnp.float32, rules)
        aopt = abstract_opt_state("adamw", defs, rules)

        def rep(shp, dt):
            return jax.ShapeDtypeStruct(
                shp, dt, sharding=NamedSharding(mesh, P(*([None] * len(shp)))))

        state = PSPState(
            server_params=aparams, opt_state=aopt, views=aviews,
            step=rep((W,), jnp.int32), busy_until=rep((W,), jnp.float32),
            pushed=rep((W,), jnp.bool_), now=rep((), jnp.float32),
            slow=rep((W,), jnp.bool_),
            key=rep((2,), jnp.uint32),
            tick=rep((), jnp.int32), total_pushes=rep((), jnp.int32),
            # fixed worker set in the dry-run: all-alive mask, empty
            # churn schedules (churn=None compiles the same program)
            alive=rep((W,), jnp.bool_),
            leave_times=rep((0,), jnp.float32),
            join_times=rep((0,), jnp.float32),
            leave_cursor=rep((), jnp.int32),
            join_cursor=rep((), jnp.int32))
        gb = shape.global_batch
        spec = P(psp_worker_axes(mesh), None, None)
        batch = {"tokens": jax.ShapeDtypeStruct(
            (W, gb // W, shape.seq_len), jnp.int32,
            sharding=NamedSharding(mesh, spec))}
        pcfg = PSPConfig(barrier="pssp", n_workers=W, sample_size=2,
                         staleness=3, straggler_frac=0.25)
        step = make_psp_train_step(cfg, pcfg, adamw(1e-4), rules)
        with use_rules(rules):
            with mesh:
                compiled = jax.jit(step).lower(state, batch).compile()
        hc = analyze_hlo(compiled.as_text())
        ma = compiled.memory_analysis()
        rec.update({
            "status": "ok",
            "wall_s": round(time.time() - t0, 2),
            "cost": {"flops": hc.flops, "bytes_accessed": hc.bytes_min},
            "collectives": {**{k: float(v) for k, v in hc.coll.items()},
                            "total": hc.coll_total},
            "memory": {"temp_bytes": int(ma.temp_size_in_bytes),
                       "argument_bytes": int(ma.argument_size_in_bytes)},
        })
        print(f"[ok] {tag}: flops/dev {hc.flops:.3e} "
              f"coll/dev {hc.coll_total:.3e}B "
              f"temp {ma.temp_size_in_bytes/1e9:.1f}GB")
    except Exception as e:  # noqa: BLE001
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"[FAIL] {tag}: {rec['error'][:200]}")
    _write(path, rec)
    return rec


def run_combo(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
              force: bool = False, verbose: bool = True) -> dict:
    tag = f"{arch}__{shape_name}__{mesh_kind}"
    path = os.path.join(out_dir, tag + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    if should_skip(arch, shape_name):
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
               "status": "skipped",
               "reason": "pure full-attention arch: long_500k requires "
                         "sub-quadratic attention (DESIGN.md §5)"}
        _write(path, rec)
        return rec

    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.devices.size
    rules = make_rules(cfg, shape, mesh)

    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "chips": int(chips), "status": "error"}
    t0 = time.time()
    try:
        with use_rules(rules):
            args, step, donate = dryrun_inputs(cfg, shape, rules)
            with mesh:
                lowered = jax.jit(step, donate_argnums=donate).lower(*args)
                t_lower = time.time() - t0
                compiled = lowered.compile()
                t_compile = time.time() - t0 - t_lower
        ma = compiled.memory_analysis()
        cost = dict(compiled.cost_analysis())
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)
        # trip-count-aware analysis (cost_analysis counts while bodies once)
        hc = analyze_hlo(hlo)
        mf = model_flops(cfg, shape)
        # memory term from the fusion-optimistic byte count (TPU-grade
        # fuser assumption); the naive count is recorded alongside
        rep = roofline_report(
            {"flops": hc.flops, "bytes accessed": hc.bytes_min},
            "", chips=chips, model_flops_total=mf)
        rep.coll_bytes = hc.coll_total
        rep.coll_detail = dict(hc.coll)
        rep.collective_s = hc.coll_total / HW().ici_bw
        terms = {"compute": rep.compute_s, "memory": rep.memory_s,
                 "collective": rep.collective_s}
        rep.bottleneck = max(terms, key=terms.get)
        rec.update({
            "status": "ok",
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "memory": {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "generated_code_bytes": int(ma.generated_code_size_in_bytes),
                "peak_bytes": int(ma.argument_size_in_bytes
                                  + ma.temp_size_in_bytes),
                "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
            },
            # raw cost_analysis values (while-loop bodies counted ONCE —
            # kept for reference only)
            "cost_counted_once": {
                "flops": float(cost.get("flops", 0.0)),
                "bytes_accessed": float(cost.get("bytes accessed", 0.0))},
            "collectives_counted_once": coll,
            # trip-count-corrected per-device totals (roofline inputs)
            "cost": {"flops": hc.flops, "bytes_accessed": hc.bytes_min,
                     "bytes_accessed_naive": hc.bytes},
            "collectives": {**{k: float(v) for k, v in hc.coll.items()},
                            "total": hc.coll_total},
            "while_trips": hc.while_trips,
            "roofline": {
                "compute_s": rep.compute_s,
                "memory_s": rep.memory_s,
                "collective_s": rep.collective_s,
                "bottleneck": rep.bottleneck,
                "useful_ratio": rep.useful_ratio,
            },
            "model_flops": mf,
            "hlo_bytes": len(hlo),
        })
        if verbose:
            print(f"[ok] {tag}: lower {t_lower:.1f}s compile {t_compile:.1f}s"
                  f" flops/dev {rec['cost']['flops']:.3e}"
                  f" coll/dev {coll['total']:.3e}B")
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(f"[FAIL] {tag}: {rec['error'].splitlines()[0][:200]}")
    _write(path, rec)
    return rec


def _write(path: str, rec: dict) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single,multi")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--psp", action="store_true",
                    help="lower the PSP train step (paper's technique) "
                         "instead of the plain pipeline")
    ap.add_argument("--workers", type=int, default=0)
    a = ap.parse_args()
    if a.psp:
        archs = ["qwen2-0.5b"] if a.arch == "all" else a.arch.split(",")
        failures = 0
        for arch in archs:
            for mesh in a.mesh.split(","):
                rec = run_psp_combo(arch, mesh, a.out, a.workers, a.force)
                failures += rec["status"] == "error"
        print(f"done; {failures} failure(s)")
        return 1 if failures else 0
    archs = list(ARCHS) if a.arch == "all" else a.arch.split(",")
    shapes = list(INPUT_SHAPES) if a.shape == "all" else a.shape.split(",")
    meshes = a.mesh.split(",")
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mesh in meshes:
                rec = run_combo(arch, shape, mesh, a.out, a.force)
                failures += rec["status"] == "error"
    print(f"done; {failures} failure(s)")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
