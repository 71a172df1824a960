"""kernels: the KDA step's share of its roofline over the traced slice;
the live rows a call from the program's counters."""

from benchmark import cells, xplane
from benchmark.layer_metrics.cache_counts import delta, layers_of


def read(name, ctx):
    got = delta(ctx)
    if ctx["trace"] is None or ctx["peaks"] is None or got is None:
        return None
    dims, layers = layers_of(ctx, "kda")
    ctx = {**ctx, "kda_dims": dims,
           "kda_rows_per_call":
               got["kda"]["state_rows"] / (got["steps"] * layers)}
    return xplane.roofline_share(
        ctx["trace"], cells.roofline("kda_step"), ctx["peaks"], ctx
    )
