"""grads_ms.train: device time of the device scope ``train.grads`` (every
worker's forward and backward, with clipping) per trainer tick of the
traced window, in milliseconds.  Ticks are the window's ``bench.step``
spans; the scopes come from ``scopes.py``.  None where the trace holds no
such scope."""
import files

scopes = files.module("scopes.py")


def read(ctx: dict):
    return scopes.per_count(ctx, ("train.grads",),
                            scopes.trainer_ticks(ctx), 1e3)
