"""retention layer: device time of the layer's own ops (the
single-token kernel ``retention_step`` and whatever runs under the
``retention.*`` named scopes: the gate, the expansion, the chunk form)
over the device's busy time in the traced slice: the share of the
chip's work that is keeping and reading the recurrent state.  An op is
under a scope where its text names it; the v5e captures of PR 37 carry
no scope in an op's text (``xplane.Trace`` keeps the HLO text alone),
so there the number is the kernel's share: a lower bound that leaves
out the chunk form."""

KERNEL = "%retention_step"
SCOPES = "retention."


def match(op: str) -> bool:
    return op.split(" = ")[0].startswith(KERNEL) or SCOPES in op


def read(name, ctx):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    events = tr.kernel_events(match)
    busy = tr.busy_s()
    if not events or busy <= 0:
        return None
    took = sum(e - s for _, s, e in events) / 1e9 / len(tr.devices)
    return 100.0 * took / busy
