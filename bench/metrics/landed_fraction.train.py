"""landed_fraction.train: gradient pushes that reached the server over the
gradients computed (workers x ticks) in the window.  The pushes are the
program's own per-tick count (``pushes`` of ``psp_apply_tick``)."""


def read(ctx: dict):
    w = ctx["window"]
    if ctx["workload"]["barrier"] == "none" or not w["ticks"]:
        return None
    return w["pushes"] / (w["workers"] * w["ticks"])
