"""conv layer: the conv layers' tails' part of the bytes of per-slot
cache the single-token steps read (``cache_bytes_read_per_token.py``),
in percent: two tokens of ``u`` a layer whatever the context, beside
keys and values that grow a token a step."""

from benchmark.layer_metrics.cache_bytes_read_per_token import moved


def read(name, ctx):
    got = moved(ctx)
    if got is None:
        return None
    kv, tails, _ = got
    return 100.0 * tails / (kv + tails) if kv + tails > 0 else None
