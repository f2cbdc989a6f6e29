"""The harness: what it refuses, and ``BENCHMARK.json`` against the files
it names."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _bench(cwd, *args, env=None):
    cmd = [sys.executable, os.path.join(cwd, "bench", "run.py"),
           "--workload", "train.qwen2-0.5b.pbsp", "--seed", "1",
           "--seconds", "1", "--trace", "0", *args]
    return subprocess.run(cmd, cwd=cwd, capture_output=True, text=True,
                          timeout=300, env={**os.environ, **(env or {})})


def test_refuses_the_cpu():
    p = _bench(run.ROOT, env={"JAX_PLATFORMS": "cpu"})
    assert p.returncode == 2, p.stderr
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(run.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench(str(tmp_path), env={"JAX_PLATFORMS": "cpu",
                                   "PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout == ""


def test_refuses_a_device_kind_without_peaks(monkeypatch):
    import jax

    class Dev:
        platform, device_kind = "tpu", "TPU v0 unknown"

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(run.NoChip, match="peaks.json"):
        run.device_info(1)
    Dev.device_kind = "TPU v5 lite"
    assert run.device_info(1)["kind"] == "TPU v5 lite"
    with pytest.raises(run.NoChip, match="asks for 4"):
        run.device_info(4)


def test_benchmark_names_its_files():
    b = run.benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"]
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert NAME.match(c["name"])
        with open(os.path.join(run.ROOT, c["file"])) as f:
            data = json.load(f)
        assert data["name"] == c["name"]
        assert data["reduced"] == c["reduced"]
        for part in ("ref", "work"):
            assert os.path.exists(os.path.join(
                run.BENCH, "configs", f"{c['name']}.{part}.py"))
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    used = set()
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        wl, cfg = run.cell(w["name"])
        assert wl["config"] == w["config"] and wl["chips"] == w["chips"]
        assert wl["why"] == w["why"]
        assert w["config"] in configs
        used.add(w["config"])
        assert os.path.exists(os.path.join(
            run.BENCH, "drivers", f"{wl['driver']}.py"))
        own = [m for m in b["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert len(own) >= 2
        assert any(w["name"] in m.get("workloads", []) for m in b["per_layer"])
    assert used == set(configs)
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(run.BENCH, "metrics",
                                           f"{m['name']}.py"))
        for name in m["workloads"]:
            assert name in e2e[m["moves"]].get("workloads", [name])


@pytest.mark.parametrize("change", [{"qkv_bias": None},
                                    {"head_dim": None},
                                    {"hidden_act": "gelu"},
                                    {"mlp_type": "moe_swiglu"}])
def test_trainer_refuses_an_architecture_the_file_does_not_state(change):
    drv = run.module("drivers", "train.py")
    _, cfg = run.cell("train.qwen2-0.5b.pbsp")
    cfg = {k: v for k, v in dict(cfg, **change).items() if v is not None}
    with pytest.raises(ValueError):
        drv.model_config(cfg)


def test_trainer_builds_the_architecture_the_file_states():
    drv = run.module("drivers", "train.py")
    _, cfg = run.cell("train.qwen2-0.5b.pbsp")
    mc = drv.model_config(cfg)
    assert (mc.head_dim, mc.qkv_bias, mc.mlp_type, mc.layer_pattern) == \
        (64, True, "swiglu", ("attn",))
    mc = drv.model_config(dict(cfg, qkv_bias=False, head_dim=128))
    assert (mc.head_dim, mc.qkv_bias) == (128, False)
