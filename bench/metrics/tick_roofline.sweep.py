"""tick_roofline.sweep: the least time the chip needs for the traced
sweeps' work, over the device's busy time in the traced window, in
percent.  The work is the algorithm's (``configs/<config>.work.py``: bytes
and FLOPs from the fleet's shapes and the sweeps' own push counts); the
least time is the larger of bytes over the HBM bandwidth and FLOPs over
the bf16 peak, both from ``peaks.json``.  The bytes bound governs at the
paper's size (about 4 FLOPs per 33 bytes per push)."""


def read(ctx: dict):
    tr, peaks = ctx["trace"], ctx["peaks"]
    if peaks is None or tr["busy_s"] <= 0:
        return None
    n = tr["counts"]
    work, cfg = ctx["work"], ctx["config"]
    least = max(work.tick_bytes(cfg, n["rows"], n["ticks"], n["pushes"])
                / peaks["hbm_bytes_per_s"],
                work.tick_flops(cfg, n["pushes"]) / peaks["bf16_flops_per_s"])
    return 100.0 * least / tr["busy_s"]
