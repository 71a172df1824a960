"""kernels: the grouped matmul's share of its roofline over the traced
slice; rows and experts touched a call from the program's counters."""

from benchmark import cells, xplane
from benchmark.layer_metrics.moe_counts import delta


def read(name, ctx):
    moe = delta(ctx)
    if ctx["trace"] is None or ctx["peaks"] is None or moe is None:
        return None
    calls = moe["expert_layer_calls"]
    ctx = {**ctx, "moe_rows_per_call": moe["assignments_held"] / calls,
           "moe_experts_per_call": moe["experts_touched"] / calls}
    return xplane.roofline_share(
        ctx["trace"], cells.roofline("grouped_matmul"), ctx["peaks"], ctx
    )
