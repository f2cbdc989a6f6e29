"""mfu.train: the model FLOPs of the landed tokens per second over the
chip's bf16 peak, in percent.  FLOPs per token come from the
configuration's work count (``configs/<config>.work.py``); tokens that
were computed but never landed, and recomputation, are not counted."""


def read(ctx: dict):
    if ctx["peaks"] is None:
        return None
    w, wl = ctx["window"], ctx["workload"]
    rate = w["landed_tokens"] / w["elapsed"]
    flops = ctx["work"].train_flops_per_token(ctx["config"], wl["seq"])
    return 100.0 * flops * rate / ctx["peaks"]["bf16_flops_per_s"]
