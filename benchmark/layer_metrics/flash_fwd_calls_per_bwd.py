"""train loop: calls of the flash forward kernel for each call of its
dq kernel in the traced slice.  A layer's backward pass calls dq once;
1.0 says each layer ran the forward kernel once (its outputs were kept
for the backward pass), 2.0 that rematerialisation ran it a second
time.  A program with no flash backward in the slice gives nothing."""


def _calls(tr, prefix: str) -> int:
    return len(tr.kernel_events(
        lambda op: op.split(" = ")[0].startswith(prefix)))


def read(name, ctx):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    bwd = _calls(tr, "%flash_dq")
    if not bwd:
        return None
    return _calls(tr, "%flash_fwd") / bwd
