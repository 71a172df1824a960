"""latent attention layer: device time of the single-token kernel
``latent_decode`` over the device's busy time in the traced slice: the
kernel alone, a lower bound on the layer's share (its projections and
its chunk form run as XLA ops, ``kda_time_share.py``)."""

from benchmark.layer_metrics.cache_counts import kernel_time_share


def read(name, ctx):
    return kernel_time_share(ctx, "latent_decode")
