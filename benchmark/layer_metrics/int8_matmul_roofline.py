"""kernels: the int8 matmul's share of its roofline over the traced slice."""

from benchmark import cells, xplane


def read(name, ctx):
    if ctx["trace"] is None or ctx["peaks"] is None:
        return None
    return xplane.roofline_share(
        ctx["trace"], cells.roofline("int8_matmul"), ctx["peaks"], ctx
    )
