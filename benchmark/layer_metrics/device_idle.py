"""device: share of the traced slice in which no op ran on the chip
(``device_idle.steady`` / ``.offline`` / ``.train``: one quantity, read
in three cells)."""


def read(name, ctx):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    window = tr.window_s(ctx["slice_s"])
    return 100.0 * (1.0 - tr.busy_s() / window)
