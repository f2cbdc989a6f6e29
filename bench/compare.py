"""The comparison that decides ``correct``: the program's readings against
the plain reference's, each number against the limit in the cell's
workload file (``limits``).

Sweep cells compare one sweep of the window, drawn from the seed, row by
row: the final step of every node, each row's total updates and control
messages (exactly), and the error trace (``error_gap``, the largest
absolute gap of a measurement point).

Training cells compare, over the ticks that set-up drove:

* ``loss_gap``: the largest relative gap of a tick's loss;
* ``grad_norm_gap``: the first gradient the optimizer got, by the worst
  leaf: ``|‖g‖ − ‖g_ref‖|`` over the larger of the reference leaf's norm
  and the median reference leaf's norm;
* ``delta_norm_gap``: the same measure of the change of the server
  parameters over the ticks;
* ``grad_sample_gap``: that first gradient entry by entry, on a fixed
  sample of each leaf's entries, by the worst leaf:
  ``‖g − g_ref‖ / ‖g_ref‖``;
* ``barrier_mismatch`` (PSP cells): the number of (tick, worker) entries
  whose step counter or push flag differs, and of ticks whose push count
  differs.

Leaves whose reference gradient norm is under :data:`NULL_LEAF` times the
median leaf's move by round-off alone (a key bias under softmax) and are
left out of both norm gaps.
"""
from __future__ import annotations

import numpy as np

#: a leaf whose reference gradient norm is below this share of the
#: median leaf's is nought to rounding, and is not compared
NULL_LEAF = 1e-3
#: entries of each leaf drawn (without replacement, fixed) for the
#: entry-wise gradient comparison
SAMPLE = 65536


def sample_index(size: int) -> np.ndarray:
    """The fixed flat indices of a leaf of ``size`` entries that are read."""
    if size <= SAMPLE:
        return np.arange(size)
    rng = np.random.default_rng(size)
    return np.sort(rng.choice(size, SAMPLE, replace=False))


def take_sample(x):
    """The sampled entries of a device array, gathered by N-d index (a
    flat view of a tiled array would make the chip relayout it whole)."""
    import jax.numpy as jnp
    idx = np.unravel_index(sample_index(x.size), x.shape)
    return x[tuple(jnp.asarray(i, jnp.int32) for i in idx)]


def sample_gap(got: list, want: list, keep) -> float:
    """Worst-leaf ``‖got − want‖ / ‖want‖`` over the sampled entries."""
    gaps = [np.linalg.norm(np.asarray(g, np.float64) - np.asarray(w))
            / max(np.linalg.norm(np.asarray(w, np.float64)), 1e-30)
            for g, w, k in zip(got, want, keep) if k]
    return float(max(gaps))


def norm_gap(got_sq, want_sq, keep) -> float:
    """Worst-leaf gap of norms given per-leaf sums of squares."""
    got = np.sqrt(np.asarray(got_sq, np.float64))
    want = np.sqrt(np.asarray(want_sq, np.float64))
    floor = np.median(want[keep])
    gap = np.abs(got - want) / np.maximum(want, floor)
    return float(np.max(gap[keep]))


def train_readings(wl: dict, seen: dict, want: dict) -> dict:
    """The numbers a training cell compares, without their limits."""
    if want.get("grad_sq") is None:
        raise ValueError("the reference landed no gradient in the checked "
                         "ticks: give the cell more check_ticks")
    ref_grad = np.sqrt(np.asarray(want["grad_sq"], np.float64))
    keep = ref_grad >= NULL_LEAF * np.median(ref_grad)
    if seen.get("grad_sq") is None:   # the program landed none: reads 1
        seen = dict(seen, grad_sq=np.zeros_like(ref_grad),
                    grad_sample=[np.zeros_like(w)
                                 for w in want["grad_sample"]])
    loss = np.asarray(seen["loss"], np.float64)
    ref_loss = np.asarray(want["loss"], np.float64)
    out = {
        "loss_gap": float(np.max(np.abs(loss - ref_loss) / np.abs(ref_loss))),
        "grad_norm_gap": norm_gap(seen["grad_sq"], want["grad_sq"], keep),
        "delta_norm_gap": norm_gap(seen["delta_sq"], want["delta_sq"], keep),
        "grad_sample_gap": sample_gap(seen["grad_sample"],
                                      want["grad_sample"], keep),
    }
    if wl["barrier"] != "none":
        steps = np.asarray(seen["steps"]) != np.asarray(want["steps"])
        pushed = np.asarray(seen["pushed"]) != np.asarray(want["pushed"])
        pushes = np.asarray(seen["pushes"]) != np.asarray(want["pushes"])
        out["barrier_mismatch"] = int(steps.sum() + pushed.sum()
                                      + pushes.sum())
    return out


def checks(wl: dict, readings: dict) -> dict:
    """``{name: (reading, limit)}``: each reading beside the cell's limit."""
    return {k: (v, wl["limits"][k]) for k, v in readings.items()}


def sweep_readings(got: dict, want: dict) -> dict:
    """The numbers a sweep cell compares: entries of the final per-node
    steps, rows' total updates and control messages that differ from the
    reference, and the largest gap of an error-trace point."""
    return {
        "steps_mismatch": int(np.sum(np.asarray(got["steps"])
                                     != np.asarray(want["steps"]))),
        "updates_mismatch": int(np.sum(np.asarray(got["updates"])
                                       != np.asarray(want["updates"]))),
        "control_mismatch": int(np.sum(np.asarray(got["control"])
                                       != np.asarray(want["control"]))),
        "error_gap": float(np.max(np.abs(
            np.asarray(got["errors"], np.float64)
            - np.asarray(want["errors"], np.float64)))),
    }
