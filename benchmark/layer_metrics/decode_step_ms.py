"""model step: device time of one decode step, from the trace: the plain
K-step dispatch programs (``jit_dispatch``) over the steps they ran, K a
dispatch.  The fused prefill+decode programs (``jit_fused``) carry a
256-token chunk beside their K steps and are left out, so that this is a
decode step's cost and not a chunk's share of it."""

from benchmark.xplane import PLAIN_DISPATCH


def read(name, ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    spans = tr.module_spans(PLAIN_DISPATCH)
    if not spans:
        return None
    k = int(ctx["cell"].config["service"]["steps_per_dispatch"])
    return sum(e - s for _, s, e in spans) / 1e6 / (len(spans) * k)
