"""KDA layer and latent attention layer: bytes of per-slot cache the
single-token steps moved (the program's own counts: every KDA state
read and written once a layer, every latent block fetched once a
layer) for each token the service emitted, over the run, in MB.  Keys
and values of 32 heads of 128 in bfloat16 would be 16,384 B a context
token a layer."""

from benchmark.layer_metrics.cache_counts import delta


def moved(ctx):
    """(KDA state bytes, latent bytes, tokens emitted) over the run, or
    None."""
    got = delta(ctx)
    if got is None or got["emitted_tokens"] <= 0:
        return None
    return (got["kda"]["state_bytes"], got["latent"]["bytes_read"],
            got["emitted_tokens"])


def read(name, ctx):
    got = moved(ctx)
    if got is None:
        return None
    state, latent, tokens = got
    return (state + latent) / tokens / 1e6
