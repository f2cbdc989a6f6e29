"""compiles_in_window.sweep: programs lowered (jit cache misses) inside the
measured and traced windows, from ``jax.monitoring`` events.  Every shape
the sweep's plan uses is warmed up in set-up, so the target is 0."""


def read(ctx: dict):
    return ctx["compiles_in_window"]
