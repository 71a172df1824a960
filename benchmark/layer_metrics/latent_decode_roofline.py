"""kernels: the latent decode's share of its roofline over the traced
slice; the live latent tokens a call from the program's counters."""

from benchmark import cells, xplane
from benchmark.layer_metrics.cache_counts import delta, layers_of


def read(name, ctx):
    got = delta(ctx)
    if ctx["trace"] is None or ctx["peaks"] is None or got is None:
        return None
    dims, layers = layers_of(ctx, "latent")
    ctx = {**ctx, "latent_dims": dims,
           "latent_tokens_per_call":
               got["latent"]["tokens_attended"] / (got["steps"] * layers)}
    return xplane.roofline_share(
        ctx["trace"], cells.roofline("latent_decode"), ctx["peaks"], ctx
    )
