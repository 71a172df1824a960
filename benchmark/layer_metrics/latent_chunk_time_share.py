"""latent attention layer: device time of the chunk form's ops
(``models/latent_attention.py`` ``latent_chunk_attention``: a query
tile's scores against a block of cached latents, their running softmax,
the weighted sum of the block) over the device's busy time in the
traced slice, in percent.

The v5e's captures carry no scope, so the ops are found by what their
text does carry: the score block's own shape, (heads, queries a tile,
keys a block) = (heads, 256, 512), which no other op of the program
has (the heads from the architecture's ``dims_of``).  XLA writes the
block with or without the leading row axis of 1 (on the capture of PR
47: the scores' fusion, the accumulator's update, whose (heads, 256,
latent) block has the same shape at a latent of 512, the final divide
and a broadcast).  The loops themselves (``while``: the walk over key
blocks, the map over query tiles) carry the block in their tuples and
span the ops inside them, which are listed too: they are not counted.
The absorbed projections on either side of the chunk form are not
counted: a lower bound on the layer's chunk share.  None without a
trace or a match."""

from benchmark import cells
from benchmark.xplane import short_op

Q_TILE, KEY_BLOCK = 256, 512
# control-flow ops span their bodies, whose ops are listed too
SPANS_ITS_BODY = ("while", "conditional", "call")


def matcher(heads: int):
    block = f"{heads},{Q_TILE},{KEY_BLOCK}]"
    return lambda op: (f"[{block}" in op or f"[1,{block}" in op) \
        and short_op(op) not in SPANS_ITS_BODY


def read(name, ctx):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    arch = cells.architecture(ctx["cell"].config)
    heads = arch.dims_of(ctx["cell"].config).get("heads")
    busy = tr.busy_s()
    if not heads or busy <= 0:
        return None
    events = tr.kernel_events(matcher(int(heads)))
    if not events:
        return None
    took = sum(e - s for _, s, e in events) / 1e9 / len(tr.devices)
    return 100.0 * took / busy
