"""psp_ms.train: device time of the PSP control plane and the views' pull
(device scopes ``train.barrier`` and ``train.pull``) per trainer tick, in
milliseconds.  None where the trace holds neither (the plain step has no
barrier)."""
import files

scopes = files.module("scopes.py")


def read(ctx: dict):
    return scopes.per_count(ctx, ("train.barrier", "train.pull"),
                            scopes.trainer_ticks(ctx), 1e3)
