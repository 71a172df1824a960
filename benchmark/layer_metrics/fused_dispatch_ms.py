"""decode engine: device time of one fused prefill+decode dispatch
(``jit_fused``: a chunk of ``prefill_chunk`` prompt tokens of the ONE
admission lane and the K decode steps of every slot), the mean over the
traced slice.  Where a request is always waiting for the lane every
dispatch is of this kind, no plain ``jit_dispatch`` runs for
``decode_step_ms`` to read, and this time over K is what a token
waits."""

FUSED_DISPATCH = r"^jit_fused\b"


def read(name, ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    spans = tr.module_spans(FUSED_DISPATCH)
    if not spans:
        return None
    return sum(e - s for _, s, e in spans) / 1e6 / len(spans)
