"""device_idle.sweep: the share of the traced window in which no operation
ran on the chip, in percent (``trace.py``)."""


def read(ctx: dict):
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
