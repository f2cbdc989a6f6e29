"""noise_us.sweep: device time of the device scope ``sweep.noise`` (the
supertick's minibatch blob, duration, score and churn draws) per grid
tick of the traced sweeps, in microseconds.  None where the trace holds
no such scope."""
import files

scopes = files.module("scopes.py")


def read(ctx: dict):
    return scopes.per_count(ctx, ("sweep.noise",), scopes.sweep_ticks(ctx),
                            1e6)
