"""latent attention layer: bytes of latent cache the single-token steps
fetched (the program's own count: every live block of every attention
block, once for keys and values alike) for each token the service
emitted, over the run, in MB.  It grows with the contexts: at 64 heads
of 128 + 128 in bfloat16, keys and values would be 32,768 B a context
token an attention block, the latent is 1,280 B as stored."""

from benchmark.layer_metrics.latent_counts import delta


def read(name, ctx):
    got = delta(ctx)
    if got is None or got["emitted_tokens"] <= 0:
        return None
    return got["bytes_read"] / got["emitted_tokens"] / 1e6
