"""kernels: the retention step's share of its roofline over the traced
slice; the live rows a call from the program's counters."""

from benchmark import cells, xplane
from benchmark.layer_metrics.retention_counts import delta


def read(name, ctx):
    got = delta(ctx)
    if ctx["trace"] is None or ctx["peaks"] is None or got is None:
        return None
    arch = cells.architecture(ctx["cell"].config)
    dims = arch.dims_of(ctx["cell"].config)
    ctx = {**ctx, "retention_dims": dims,
           "retention_rows_per_call":
               got["state_rows"] / (got["steps"] * dims["layers"])}
    return xplane.roofline_share(
        ctx["trace"], cells.roofline("retention_step"), ctx["peaks"], ctx
    )
