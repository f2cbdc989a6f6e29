"""data_plane_us.sweep: device time of the device scope
``sweep.data_plane`` (the XLA data plane: residual, finishers' gradient
sum, the views' pull) per grid tick of the traced sweeps, in
microseconds.  None where the trace holds no such scope."""
import files

scopes = files.module("scopes.py")


def read(ctx: dict):
    return scopes.per_count(ctx, ("sweep.data_plane",),
                            scopes.sweep_ticks(ctx), 1e6)
