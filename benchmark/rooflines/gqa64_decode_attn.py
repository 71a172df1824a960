"""``ops/pallas/decode_attention.py``, single-token decode over the int8
KV cache, at a model whose heads are narrower than the cache's lanes:
bound by the bytes of the LIVE keys and values at the PUBLISHED width.

What the mathematics needs, whatever implements it (NOT the op's
shapes: a head of 64 stored zero-padded in 128 lanes does the same work
in twice the bytes and must read a LOWER share, never one above 100%).
For every live token and KV head: int8 K and V (2 x head_dim bytes) and
their two bfloat16 scales (4 bytes); the products are q . k and p . v
over the query heads, 2 x 2 x head_dim operations a token and query
head.  Heads and widths are the architecture's (``ctx["gqa_dims"]``,
its ``dims_of``); ``ctx["kv_live_tokens"]`` is the mean number of live
tokens over all slots during the traced slice, from the requests' own
clocks.  Each matched op is one attention layer's step over all of
them."""


def match(op: str) -> bool:
    head = op.split(" = ")[0]
    return head.startswith("%decode_attention") and "chunk" not in head


def cost(op: str, ctx):
    d = ctx["gqa_dims"]
    live = float(ctx["kv_live_tokens"])
    nbytes = live * d["kv_heads"] * (2 * d["head_dim"] + 4)
    flops = 2.0 * 2.0 * live * d["heads"] * d["head_dim"]
    return flops, nbytes
