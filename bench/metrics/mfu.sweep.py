"""mfu.sweep: the FLOPs the window's sweeps needed
(``configs/<config>.work.py``: the residual and gradient of every push)
per second of the window, over the chip's bf16 peak, in percent.  The
sweep is bound by bytes, so this stays far below 1%; it bounds what a
change that takes the kernel off the path may claim."""


def read(ctx: dict):
    if ctx["peaks"] is None:
        return None
    w = ctx["window"]
    flops = ctx["work"].tick_flops(ctx["config"], w["updates"])
    return 100.0 * flops / w["elapsed"] / ctx["peaks"]["bf16_flops_per_s"]
