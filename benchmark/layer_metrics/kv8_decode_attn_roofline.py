"""kernels: the int8-KV decode attention's share of its roofline.

The live KV it has to read is worked out here from the requests' own
clocks: a request holds its prompt plus the tokens streamed so far from
its first token to its last."""

from benchmark import cells, xplane


def live_tokens(reqs, lo: float, hi: float) -> float:
    """Mean over [lo, hi] of the live tokens summed over requests."""
    total = 0.0
    for r in reqs:
        t1, t2, n = r.stream.t_first, r.stream.t_last, r.stream.n
        if t1 is None or t2 <= lo or t1 >= hi:
            continue
        a, b = max(t1, lo), min(t2, hi)
        rate = (n - 1) / (t2 - t1) if t2 > t1 else 0.0
        mid = 0.5 * (a + b) - t1
        total += (len(r.ids) + 1 + rate * mid) * (b - a)
    return total / (hi - lo)


def read(name, ctx):
    tr, (lo, hi) = ctx["trace"], ctx["slice"]
    if tr is None or ctx["peaks"] is None or lo is None:
        return None
    ctx = {**ctx, "kv_live_tokens": live_tokens(ctx["window"]["reqs"], lo, hi)}
    return xplane.roofline_share(
        tr, cells.roofline("kv8_decode_attn"), ctx["peaks"], ctx
    )
