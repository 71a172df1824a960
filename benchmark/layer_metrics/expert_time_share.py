"""expert layer: device time of the grouped matmul's calls over the
device's busy time in the traced slice: the share of the chip's work
that is the experts' products."""

from benchmark import cells


def read(name, ctx):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    events = tr.kernel_events(cells.roofline("grouped_matmul").match)
    busy = tr.busy_s()
    if not events or busy <= 0:
        return None
    took = sum(e - s for _, s, e in events) / 1e9 / len(tr.devices)
    return 100.0 * took / busy
