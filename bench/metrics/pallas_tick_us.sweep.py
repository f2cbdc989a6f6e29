"""pallas_tick_us.sweep: device time of the Pallas tick kernel's events
(the workload's ``kernel``, an HLO instruction ``%psp_tick.<n>``) over the
grid ticks the traced sweeps ran, in microseconds.  None where the trace
holds no such event (a tick implementation without the kernel)."""
import files


def read(ctx: dict):
    tr = ctx["trace"]
    secs = files.module("trace.py").op_seconds(tr["ops"],
                                               ctx["workload"]["kernel"])
    ticks = tr["counts"]["ticks"]
    if secs <= 0 or not ticks:
        return None
    return 1e6 * secs / ticks
