"""KDA layer: device time of the single-token kernel ``kda_step`` over
the device's busy time in the traced slice: the kernel alone, a lower
bound on the layer's share (its projections, convolution, gates and
chunk form run as XLA ops whose text carries no scope on the v5e
captures, ``retention_time_share.py``)."""

from benchmark.layer_metrics.cache_counts import kernel_time_share


def read(name, ctx):
    return kernel_time_share(ctx, "kda_step")
