"""optimizer_ms.train: device time of the device scope ``train.optimizer``
per trainer tick, in milliseconds: the PSP server's masked update (paid
on every tick, whether a push lands or not) or the plain step's update.
None where the trace holds no such scope."""
import files

scopes = files.module("scopes.py")


def read(ctx: dict):
    return scopes.per_count(ctx, ("train.optimizer",),
                            scopes.trainer_ticks(ctx), 1e3)
