"""The work of a PSP sweep, counted from the fleet's shapes and the sweep's
own counts of pushes, never from what an implementation runs.

A push (a node's finished step) reads the node's minibatch of ``m`` rows
of ``d`` features and a label each and the view it pulled (``d``), and
computes the residual and the gradient: ``2·m·d`` FLOPs each.  A pull
writes the server model into the node's view (``d``); every step that
lands is followed by its node's next start, so pulls are counted as
pushes (the count is off by at most the nodes still computing at the
horizon, under 1% at the paper's size).  Each grid tick reads every
node's next event time (4 bytes) to find its finishers.  The server's
update of ``d`` floats per row and tick is left out: it is under 0.5% of
the bytes.
"""
from __future__ import annotations


def tick_bytes(cfg: dict, rows: int, ticks: int, pushes: int) -> int:
    """Least bytes the data and control planes move over ``ticks``."""
    P, d, m = cfg["n_nodes"], cfg["dim"], cfg["batch"]
    per_push = 4 * (m * (d + 1) + d)      # minibatch, labels, view
    per_pull = 4 * d                      # the view written
    return pushes * (per_push + per_pull) + ticks * rows * P * 4


def tick_flops(cfg: dict, pushes: int) -> int:
    """FLOPs of the pushes' residuals and gradients."""
    return pushes * 4 * cfg["batch"] * cfg["dim"]
