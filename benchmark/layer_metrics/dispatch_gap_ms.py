"""decode engine: what the host costs the chip per dispatch — the median
gap on the device between the end of one dispatch program (``jit_dispatch``
or the fused prefill+decode ``jit_fused``) and the start of the next."""

from benchmark import stats

from benchmark.xplane import DISPATCH_PROGRAMS


def read(name, ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    spans = sorted(tr.module_spans(DISPATCH_PROGRAMS), key=lambda s: s[1])
    gaps = [
        (b[1] - a[2]) / 1e6 for a, b in zip(spans, spans[1:]) if b[1] > a[2]
    ]
    return stats.median(gaps) if gaps else None
