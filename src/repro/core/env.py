"""Typed registry for every ``PSP_*`` environment override.

The env-override surface grew one variable at a time (sweep mesh, tick
impl, trace stride, compile cache, hypothesis budget, ...) with each read
site doing its own ``os.environ.get`` + ad-hoc parsing.  This module is
the single source of truth: every override is declared once in
:data:`REGISTRY` with its type, default and one-line description, and
every read site goes through the typed accessors below.  Benefits:

* a mistyped variable name raises ``KeyError`` at the read site instead
  of silently reading the process default;
* the docs table is *generated* from the registry
  (``python -m repro.core.env``), so it cannot drift — the serving-tier
  docs gate (``tests/test_env.py``) pins every registered name into
  ``docs/ARCHITECTURE.md``;
* parsing is uniform: ``int``/``float`` variables reject garbage with a
  message naming the variable, and *flag* variables follow one rule
  (set-and-nonempty = true — ``PSP_REGEN_GOLDEN=1``) everywhere.

Accessors return the registered default when the variable is unset; the
empty string counts as unset (so ``PSP_SWEEP_MESH= python ...`` clears an
ambient override).  Write sites (benchmarks exporting a mesh for child
code) still use ``os.environ`` directly — the registry types *reads*.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, Optional

__all__ = ["EnvVar", "REGISTRY", "get_str", "get_int", "get_float", "flag",
           "markdown_table"]


@dataclasses.dataclass(frozen=True)
class EnvVar:
    """One registered environment override."""

    name: str           #: full variable name (``PSP_...``)
    kind: str           #: "str" | "int" | "float" | "flag"
    default: Any        #: value returned when unset (flags: False)
    help: str           #: one-line description for the generated table


def _reg(*vs: EnvVar) -> Dict[str, EnvVar]:
    return {v.name: v for v in vs}


REGISTRY: Dict[str, EnvVar] = _reg(
    EnvVar("PSP_SWEEP_MESH", "str", None,
           "`RxN` rows×nodes mesh factorization for jax sweeps "
           "(beats `PSP_SWEEP_DEVICES`; e.g. `4x2`)"),
    EnvVar("PSP_SWEEP_DEVICES", "int", None,
           "rows-axis device count for 1-D sweep placement "
           "(default: every local device; `0` = default)"),
    EnvVar("PSP_SWEEP_CHUNK", "int", None,
           "force a uniform sweep scan-chunk length in records "
           "(default: greedy pow2 schedule)"),
    EnvVar("PSP_TRACE_STRIDE", "int", None,
           "force the sweep trace record stride (snapped down to an "
           "admissible divisor of the measurement cadence)"),
    EnvVar("PSP_TICK_IMPL", "str", "auto",
           "PSP tick kernel dispatch: `auto` | `pallas` | `interpret` "
           "| `ref`"),
    EnvVar("PSP_NO_COMPILE_CACHE", "flag", False,
           "turn off the persistent JAX compile cache (default on, at "
           "`JAX_COMPILATION_CACHE_DIR` or `<checkout>/.jax_cache`)"),
    EnvVar("PSP_BENCH_HOST_DEVICES", "int", None,
           "forced host-device count for CPU benchmark runs "
           "(`0` disables the forced mesh; default: one per core, "
           "capped at 8)"),
    EnvVar("PSP_HYP_EXAMPLES", "int", 10,
           "hypothesis example budget for the property suites "
           "(CI fast lanes set 4)"),
    EnvVar("PSP_REGEN_GOLDEN", "flag", False,
           "regenerate committed golden trace files instead of "
           "comparing against them (intentional-change workflow)"),
    EnvVar("PSP_FAULT_PLAN", "str", None,
           "default fault plan for the cluster harness / chaos bench: a "
           "registry spec (`standard:seed=7`) or a plan-JSON path"),
    EnvVar("PSP_BUS_BACKOFF_BASE", "float", 0.25,
           "snapshot-watcher retry backoff base seconds for a bad "
           "step (doubles per failure, jittered)"),
    EnvVar("PSP_BUS_BACKOFF_MAX", "float", 8.0,
           "snapshot-watcher retry backoff ceiling in seconds"),
    EnvVar("PSP_BUS_BLACKLIST_MAX", "int", 64,
           "max bad-step entries the snapshot watcher remembers "
           "(oldest evicted beyond the cap)"),
    EnvVar("PSP_BUS_BLACKLIST_TTL", "float", 300.0,
           "seconds a bad-step entry stays blacklisted before eviction "
           "(the retention window)"),
    EnvVar("PSP_HB_INTERVAL", "float", 0.25,
           "cluster worker heartbeat-sidecar write cadence in seconds"),
    EnvVar("PSP_HB_TIMEOUT", "float", 10.0,
           "heartbeat staleness after which the cluster coordinator "
           "SIGKILLs a hung worker and treats it as departed"),
)


def _raw(name: str) -> Optional[str]:
    """Registered lookup: the raw string, or None when unset/empty."""
    if name not in REGISTRY:
        raise KeyError(f"{name} is not a registered env override "
                       f"(known: {sorted(REGISTRY)})")
    val = os.environ.get(name)
    return val if val else None


def get_str(name: str) -> Optional[str]:
    """String-typed read of a registered override (default when unset)."""
    var = REGISTRY[name] if name in REGISTRY else None
    raw = _raw(name)
    return var.default if raw is None else raw


def get_int(name: str) -> Optional[int]:
    """Int-typed read; garbage raises ``ValueError`` naming the variable."""
    raw = _raw(name)
    if raw is None:
        return REGISTRY[name].default
    try:
        return int(raw)
    except ValueError:
        raise ValueError(f"{name}={raw!r} is not an integer") from None


def get_float(name: str) -> Optional[float]:
    """Float-typed read; garbage raises ``ValueError`` naming the variable."""
    raw = _raw(name)
    if raw is None:
        return REGISTRY[name].default
    try:
        return float(raw)
    except ValueError:
        raise ValueError(f"{name}={raw!r} is not a number") from None


def flag(name: str) -> bool:
    """Flag-typed read: set to any non-empty value = True."""
    return _raw(name) is not None


def markdown_table() -> str:
    """The docs table, generated from :data:`REGISTRY` (one row per var)."""
    rows = ["| variable | type | default | meaning |",
            "|---|---|---|---|"]
    for v in REGISTRY.values():
        default = "unset" if v.default in (None, False) else str(v.default)
        help_ = v.help.replace("|", "\\|")   # keep cell pipes out of the grid
        rows.append(f"| `{v.name}` | {v.kind} | {default} | {help_} |")
    return "\n".join(rows)


if __name__ == "__main__":
    print(markdown_table())
