"""compiles_in_window.train: programs lowered (jit cache misses) inside the
measured and traced windows of a trainer cell, from ``jax.monitoring``
events.  Set-up drives the one step through its checked ticks, so the
target is 0: a recompile inside the window is a stall of seconds."""


def read(ctx: dict):
    return ctx["compiles_in_window"]
