"""model step: device time of the attention kernels' calls (the
single-token ``decode_attention``, its chunk form and the flash forward
a fresh chunk takes) over the device's busy time in the traced slice:
the share of the chip's work that is reading keys and values."""

ATTENTION_OPS = ("%decode_attention", "%flash_fwd_kernel")


def match(op: str) -> bool:
    return op.split(" = ")[0].startswith(ATTENTION_OPS)


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
