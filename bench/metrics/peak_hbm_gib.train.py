"""peak_hbm_gib.train: ``peak_bytes_in_use`` of the chip after the window,
in GiB."""


def read(ctx: dict):
    if ctx["peak_bytes"] is None:
        return None
    return ctx["peak_bytes"] / 2 ** 30
