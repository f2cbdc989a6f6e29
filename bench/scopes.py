#!/usr/bin/env python3
"""The program's own names in a profiler trace: device scopes, host spans.

The program names its work on the chip with ``jax.named_scope``
(:data:`SCOPES`) and its host phases with ``jax.profiler.TraceAnnotation``
(:data:`HOST_SPANS`).  A device op's scope is part of its op path, the
``op_name`` metadata XLA keeps on the instruction; the ``.xplane.pb``
holds it as the ``tf_op`` stat of the op's event metadata.
``jax.profiler.ProfileData`` does not expose event metadata, so this
module decodes the XPlane protobuf itself (:func:`xspace`, with
``google.protobuf`` from a schema declared here).

:func:`reduce` reads one trace on the clock and window of ``trace.py``
(first to last harness span, ``bench.*``) and returns:

* ``scopes`` — device seconds by scope: each instant of the window in
  which an op runs goes to the innermost op running (:func:`innermost`:
  a leaf op's whole time, a loop frame's uncovered time), and from it to
  the innermost scope of its op path; an op outside every scope goes to
  its instruction's name where that is a named kernel (``kernels``, e.g.
  ``psp_tick``), else to ``unscoped``; averaged over the devices used.
  The buckets sum to ``busy_s``;
* ``host`` — seconds and counts of the program's spans, by name;
* ``span_counts`` — the harness spans, by name;
* ``idle`` — each stretch of the window in which no device op runs,
  charged to the innermost span that overlaps it most: a program span
  before a harness span, ``other`` where none does;
* ``top_ops`` — the largest ops of each of those buckets.

Run as a script it measures one cell by these names::

    python3 bench/scopes.py --workload <name> --seed <n> --seconds <s>

It sets the cell up through its driver, runs an untraced window of
``--seconds``, then the workload's ``trace_seconds`` under the profiler
(as a ``--trace 1`` run of ``run.py`` does), and prints a
``trace:`` line (idle by innermost span) on standard error and one JSON
object: the scoped per-layer metrics (``bench/metrics/<name>.py`` readers
of :data:`METRICS`), the accepted ones the cell lists, the scope and span
totals (``attributed_s``, their sum, matches ``busy_s``), and the median
call in the untraced window and in the traced stretch (the cost of
tracing).
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import re
import shutil
import statistics
import sys
import time

_BENCH = os.path.dirname(os.path.abspath(__file__))
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

import files  # noqa: E402

trace = files.module("trace.py")

#: device scopes (``jax.named_scope``) that the program puts on its work
SCOPES = ("train.grads", "train.optimizer", "train.barrier", "train.pull",
          "sweep.noise", "sweep.data_plane")
#: host spans (``jax.profiler.TraceAnnotation``) that the program records
HOST_SPANS = ("sweep.init", "sweep.stage", "sweep.dispatch", "sweep.fetch",
              "sweep.results")
#: the bucket of an op that lies in no scope and is no named kernel
UNSCOPED = "unscoped"
#: per-layer metrics whose readers take what :func:`reduce` returns
METRICS = ("grads_ms.train", "optimizer_ms.train", "psp_ms.train",
           "noise_us.sweep", "data_plane_us.sweep", "sweep_host_ms.sweep")

#: the part of the XPlane schema (``tsl/profiler/protobuf/xplane.proto``)
#: that is read here: (field, number, type, repeated).  Strings are read as
#: bytes, so a name that is not UTF-8 cannot fail the parse; a map is its
#: wire form, a repeated key/value entry.
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, "bytes", False), ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True)],
    "EventMetadataEntry": [("key", 1, "int64", False),
                           ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, "int64", False),
                          ("value", 2, "XStatMetadata", False)],
    "XLine": [("name", 2, "bytes", False), ("timestamp_ns", 3, "int64", False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int64", False),
               ("offset_ps", 2, "int64", False),
               ("duration_ps", 3, "int64", False)],
    "XStat": [("metadata_id", 1, "int64", False),
              ("str_value", 5, "bytes", False),
              ("ref_value", 7, "uint64", False)],
    "XEventMetadata": [("id", 1, "int64", False), ("name", 2, "bytes", False),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("id", 1, "int64", False), ("name", 2, "bytes", False)],
}


@functools.lru_cache(maxsize=1)
def _xspace_class():
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    scalar = {"int64": F.TYPE_INT64, "uint64": F.TYPE_UINT64,
              "bytes": F.TYPE_BYTES}
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    for msg, fields in _SCHEMA.items():
        m = fd.message_type.add(name=msg)
        for name, number, typ, repeated in fields:
            f = m.field.add(name=name, number=number,
                            label=F.LABEL_REPEATED if repeated
                            else F.LABEL_OPTIONAL)
            if typ in scalar:
                f.type = scalar[typ]
            else:
                f.type, f.type_name = F.TYPE_MESSAGE, f".bench_xplane.{typ}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def xspace(path: str):
    """The decoded ``XSpace`` of an ``.xplane.pb`` file."""
    with open(path, "rb") as f:
        return _xspace_class().FromString(f.read())


def _text(b: bytes) -> str:
    return b.decode("utf-8", "replace")


def _line_events(line, names):
    """(name, start_ns, end_ns, metadata id) of a line's events, in whole
    nanoseconds as ``ProfileData`` gives them."""
    t0 = line.timestamp_ns
    for ev in line.events:
        s = t0 + ev.offset_ps // 1000
        yield names[ev.metadata_id], s, s + ev.duration_ps // 1000, \
            ev.metadata_id


def planes(path: str):
    """(host spans, {device plane: [(name, start, end, op path)]}).

    Host spans are the harness's (``bench.*``) and the program's
    (:data:`HOST_SPANS`) on every host plane; device events are those of
    each device plane's ``XLA Ops`` line, with the ``tf_op`` stat of their
    metadata ('' where it has none).  Times are in nanoseconds.
    """
    spans, devices = [], {}
    for pl in xspace(path).planes:
        name = _text(pl.name)
        meta = {e.key: e.value for e in pl.event_metadata}
        names = {k: _text(m.name) for k, m in meta.items()}
        if name.startswith("/device:"):
            stat = {e.key: _text(e.value.name) for e in pl.stat_metadata}

            def op_path(m):
                for st in m.stats:
                    if stat.get(st.metadata_id) == "tf_op":
                        return (_text(st.str_value) if st.str_value
                                else stat.get(st.ref_value, ""))
                return ""

            paths = {}
            evs = []
            for line in pl.lines:
                if _text(line.name) != trace.OPS_LINE:
                    continue
                for n, s, e, mid in _line_events(line, names):
                    if mid not in paths:
                        paths[mid] = op_path(meta[mid])
                    evs.append((n, s, e, paths[mid]))
            if evs:
                devices[name] = evs
        elif name.startswith("/host:"):
            for line in pl.lines:
                spans.extend((n, s, e) for n, s, e, _ in
                             _line_events(line, names)
                             if n.startswith(trace.SPAN_PREFIX)
                             or n in HOST_SPANS)
    return spans, devices


_TOKEN = re.compile(r"[\w.\-]+")


def scope_of(op_path: str) -> str | None:
    """The innermost of :data:`SCOPES` that is a component of ``op_path``.

    Transform wrappers (``vmap(train.grads)``, ``transpose(jvp(...))``)
    are peeled by reading the path's names in order, so the last scope
    named is the innermost.
    """
    found = None
    for tok in _TOKEN.findall(op_path):
        if tok in SCOPES:
            found = tok
    return found


def instruction(name: str) -> str:
    """The HLO instruction of an event name ``%name.3 = ...``, without its
    number (as ``trace.op_seconds`` reads it)."""
    return name.split(" = ", 1)[0].lstrip("%").split(".", 1)[0]


def bucket(name: str, op_path: str, kernels=()) -> str:
    """Where an op's time goes: its scope, else its kernel, else
    :data:`UNSCOPED`."""
    s = scope_of(op_path)
    if s is not None:
        return s
    inst = instruction(name)
    return inst if inst in kernels else UNSCOPED


def innermost(evs, lo: float, hi: float) -> dict[int, float]:
    """Each instant of ``[lo, hi]`` in which an event of ``evs`` runs,
    given to the innermost one running then (the latest started);
    ``{index in evs: time}``.

    A leaf op gets its whole clipped time, as in ``trace.leaves``; a
    frame (a ``while`` loop's event spans its body's ops) gets the time
    its ops leave uncovered.  The times sum to the busy union of ``evs``
    in the window.
    """
    out: dict[int, float] = {}
    stack: list[int] = []
    t = lo

    def run_until(x):
        nonlocal t
        while stack and t < x:
            i = stack[-1]
            end = evs[i][2]
            if end > t:
                u = min(end, x)
                d = min(u, hi) - max(t, lo)
                if d > 0:
                    out[i] = out.get(i, 0.0) + d
                t = u
            if end <= t:
                stack.pop()
        t = max(t, x)

    for i in sorted(range(len(evs)), key=lambda i: (evs[i][1], -evs[i][2])):
        run_until(evs[i][1])
        stack.append(i)
    run_until(float("inf"))
    return out


def _labels(gap_list, spans) -> list:
    """For each gap, the name of the span that overlaps it most (None
    where none does); the spans must not nest each other."""
    spans = sorted(spans, key=lambda x: x[1])
    out, j = [], 0
    for g in gap_list:
        while j < len(spans) and spans[j][2] <= g[0]:
            j += 1
        best, name = 0.0, None
        k = j
        while k < len(spans) and spans[k][1] < g[1]:
            ov = min(g[1], spans[k][2]) - max(g[0], spans[k][1])
            if ov > best:
                best, name = ov, spans[k][0]
            k += 1
        out.append(name)
    return out


def attribute(gap_list, program, harness) -> dict[str, float]:
    """Idle time summed by the innermost span overlapping each gap: the
    program span that overlaps it most, else the harness span, else
    ``other``."""
    out: dict[str, float] = {}
    for g, p, h in zip(gap_list, _labels(gap_list, program),
                       _labels(gap_list, harness)):
        name = p or h or "other"
        out[name] = out.get(name, 0.0) + (g[1] - g[0])
    return out


def reduce(path: str, n_devices: int, kernels=(), top: int = 5) -> dict:
    """Scopes, program spans and idle attribution of one trace.

    The window, the devices and the busy time are ``trace.reduce``'s:
    ``window_s`` and ``busy_s`` are returned beside ``scopes``, ``host``
    (``{name: {"s": seconds, "n": count}}``), ``span_counts``, ``idle``
    (seconds by innermost span) and ``top_ops`` (``{bucket: [[op,
    seconds], ...]}``, the ``top`` largest of each).  Seconds are averaged
    over the ``n_devices`` device planes.
    """
    spans, devices = planes(path)
    harness = [s for s in spans if s[0].startswith(trace.SPAN_PREFIX)]
    program = [s for s in spans if s[0] in HOST_SPANS]
    if not harness:
        raise ValueError(f"{path}: no harness spans ({trace.SPAN_PREFIX}*)")
    names = sorted(devices)[:n_devices]
    if len(names) < n_devices:
        raise ValueError(f"{path}: {len(names)} device planes with ops, "
                         f"need {n_devices}")
    lo = min(s for _, s, _ in harness)
    hi = max(e for _, _, e in harness)
    busy_ns, scopes, idle, ops = 0.0, {}, {}, {}
    for dev in names:
        evs = devices[dev]
        busy = trace.union(trace.clip([(s, e) for _, s, e, _ in evs],
                                      lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        for i, d in innermost(evs, lo, hi).items():
            n, _, _, p = evs[i]
            b = bucket(n, p, kernels)
            scopes[b] = scopes.get(b, 0.0) + d
            ops.setdefault(b, {})
            ops[b][n] = ops[b].get(n, 0.0) + d
        for k, d in attribute(trace.gaps(busy, lo, hi), program,
                              harness).items():
            idle[k] = idle.get(k, 0.0) + d
    n = float(len(names))
    host: dict[str, dict] = {}
    for name, s, e in program:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            h = host.setdefault(name, {"s": 0.0, "n": 0})
            h["s"] += d / 1e9
            h["n"] += 1
    counts: dict[str, int] = {}
    for name, _, _ in harness:
        counts[name] = counts.get(name, 0) + 1
    return {
        "window_s": (hi - lo) / 1e9, "busy_s": busy_ns / n / 1e9,
        "scopes": {k: v / n / 1e9 for k, v in scopes.items()},
        "host": host, "span_counts": counts,
        "idle": {k: v / n / 1e9 for k, v in idle.items()},
        "top_ops": {b: [[k, v / n / 1e9] for k, v in
                        sorted(o.items(), key=lambda kv: -kv[1])[:top]]
                    for b, o in ops.items()}}


def idle_line(r: dict) -> str:
    """The ``trace:`` line: idle time by innermost span, largest first."""
    idle = sorted(r["idle"].items(), key=lambda kv: -kv[1])
    parts = ", ".join(f"{k} {v!r} s" for k, v in idle)
    return (f"trace: idle {sum(r['idle'].values())!r} s of "
            f"{r['window_s']!r} s, by innermost span: {parts or 'none'}")


# ---------------------------------------------------------------------- #
# readers' helpers


def per_count(ctx: dict, names, count: float, scale: float):
    """Device seconds of the scopes ``names`` over ``count``, times
    ``scale``; None where the trace holds none of them or nothing ran."""
    got = [ctx["trace"].get("scopes", {}).get(k) for k in names]
    if not count or all(v is None for v in got):
        return None
    return scale * sum(v for v in got if v is not None) / count


def trainer_ticks(ctx: dict) -> int:
    """The trainer ticks in the traced window (its ``bench.step`` spans)."""
    return ctx["trace"].get("span_counts", {}).get("bench.step", 0)


def sweep_ticks(ctx: dict) -> int:
    """The grid ticks the traced sweeps ran."""
    return ctx["trace"].get("counts", {}).get("ticks", 0)


# ---------------------------------------------------------------------- #
# the measurement


def measure(name: str, seed: int, seconds: float, trace_seconds: float,
            devices: dict) -> dict:
    """Set up, run and trace one cell; the result object."""
    import jax

    import run
    wl, cfg = run.cell(name)
    drv = files.module("drivers", f"{wl['driver']}.py")
    compiles = run.CompileCounter()
    t0 = time.perf_counter()
    sess = drv.setup(wl, cfg, seed)
    setup_s = time.perf_counter() - t0
    compiles.active = True
    win = sess.window(seconds)
    trainer = wl["driver"] == "train"
    trace_dir = os.path.join(run.ROOT, ".bench_trace", f"{name}.scopes")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    try:
        stretch = (sess._ticks if trainer else sess._sweeps)(trace_seconds)
    finally:
        jax.profiler.stop_trace()
    try:
        path = trace.find_xplane(trace_dir)
        kernels = (wl["kernel"],) if "kernel" in wl else ()
        tr = trace.reduce(path, wl["chips"])
        sc = reduce(path, wl["chips"], kernels)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    compiles.active = False
    if not trainer:      # as the sweep driver's traced() counts them
        n = len(stretch["sweeps"])
        tr["counts"] = {"sweeps": n, "rows": sess.n_rows,
                        "ticks": n * sess._n_ticks(),
                        "pushes": stretch["updates"]}
    tr.update(scopes=sc["scopes"], host=sc["host"],
              span_counts=sc["span_counts"])
    used = jax.devices()[:wl["chips"]]
    ctx = {"workload": wl, "config": cfg, "window": win, "trace": tr,
           "compiles_in_window": compiles.count,
           "peak_bytes": run.peak_bytes(used),
           "peaks": files.load_json("peaks.json").get(devices["kind"]),
           "work": files.module("configs", f"{wl['config']}.work.py")}
    scoped = {}
    for m in METRICS:
        v = files.module("metrics", f"{m}.py").read(ctx)
        if v is not None:
            scoped[m] = v
    print(idle_line(sc), file=sys.stderr)
    kernel = sum(sc["scopes"].get(k, 0.0) for k in kernels)
    return {
        "workload": name, "seed": seed, "device": devices,
        "setup_s": setup_s,
        "metrics": scoped,
        "accepted": {k: v["value"] for k, v in
                     run.per_layer(run.benchmark(), name, ctx).items()},
        "busy_s": sc["busy_s"], "window_s": sc["window_s"],
        "attributed_s": sum(sc["scopes"].values()), "kernel_s": kernel,
        "unscoped_share": sc["scopes"].get(UNSCOPED, 0.0) / sc["busy_s"],
        "scopes": sc["scopes"], "host": sc["host"], "idle": sc["idle"],
        "span_counts": sc["span_counts"], "top_ops": sc["top_ops"],
        "calls": {"untraced_n": len(win["call_s"]),
                  "untraced_median_s": statistics.median(win["call_s"]),
                  "traced_n": len(stretch["call_s"]),
                  "traced_median_s": statistics.median(stretch["call_s"])},
        "compiles_in_window": compiles.count,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args(argv)
    import run
    try:
        wl, _ = run.cell(a.workload)
    except FileNotFoundError as e:
        print(f"scopes: unknown workload {a.workload!r} ({e})",
              file=sys.stderr)
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    try:
        devices = run.device_info(wl["chips"])
    except run.NoChip as e:
        print(f"scopes: {e}", file=sys.stderr)
        return 2
    run.enable_cache()
    out = measure(a.workload, a.seed, a.seconds, wl["trace_seconds"],
                  devices)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
