"""kernels: flash attention forward+backward, share of the bf16 peak."""

from benchmark import cells, xplane


def read(name, ctx):
    if ctx["trace"] is None or ctx["peaks"] is None:
        return None
    return xplane.roofline_share(
        ctx["trace"], cells.roofline("flash_fwd_bwd"), ctx["peaks"], ctx
    )
