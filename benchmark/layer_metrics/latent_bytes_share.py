"""latent attention layer: the latent cache's part of the bytes of
per-slot cache the single-token steps moved
(``cache_bytes_per_token.py``), in percent: it grows with the contexts,
the KDA states' part does not."""

from benchmark.layer_metrics.cache_bytes_per_token import moved


def read(name, ctx):
    got = moved(ctx)
    if got is None:
        return None
    state, latent, _ = got
    return 100.0 * latent / (state + latent)
