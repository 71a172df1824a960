"""retention layer: bytes of recurrent state the single-token steps
moved (the program's own count, summed over layers) for each token the
service emitted, over the run, in MB.  One pass over a slot's state
reads and writes it once a layer: a second pass (the new state written,
then read again for the query) would read half as much again."""

from benchmark.layer_metrics.retention_counts import delta


def read(name, ctx):
    got = delta(ctx)
    if got is None or got["emitted_tokens"] <= 0:
        return None
    return got["state_bytes"] / got["emitted_tokens"] / 1e6
