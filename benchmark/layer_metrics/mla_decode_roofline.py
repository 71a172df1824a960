"""kernels: the latent decode's share of its roofline over the traced
slice, in a stack whose only cache is latents: heads and widths from
the architecture's ``dims_of`` (``rooflines/latent_decode.py`` as it
is), the live latent tokens a call from the program's counters, a call
being one attention block of one step (``dims["mixers"]`` blocks a
step: two a shortcut layer)."""

from benchmark import cells, xplane
from benchmark.layer_metrics.latent_counts import delta


def read(name, ctx):
    got = delta(ctx)
    if ctx["trace"] is None or ctx["peaks"] is None or got is None:
        return None
    arch = cells.architecture(ctx["cell"].config)
    dims = arch.dims_of(ctx["cell"].config)
    ctx = {**ctx, "latent_dims": dims,
           "latent_tokens_per_call":
               got["tokens_attended"] / (got["steps"] * dims["mixers"])}
    return xplane.roofline_share(
        ctx["trace"], cells.roofline("latent_decode"), ctx["peaks"], ctx
    )
