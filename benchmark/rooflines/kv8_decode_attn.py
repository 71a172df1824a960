"""``ops/pallas/decode_attention.py``, single-token decode over the int8
KV cache: bound by the bytes of the LIVE keys and values.

The buffer the kernel is handed is the whole (slots, kv heads, buffer,
head dim) cache; what the algorithm needs is the live part: for every
live token int8 K and V (2 * head_dim bytes a kv head) and their two
bfloat16 scales.  ``ctx["kv_live_tokens"]`` is the mean number of live
tokens over all slots during the traced slice, worked out by the reader
from the requests' own clocks."""

from benchmark.xplane import hlo_shapes


def match(op: str) -> bool:
    head = op.split(" = ")[0]
    return head.startswith("%decode_attention") and "chunk" not in head


def cost(op: str, ctx):
    shapes = hlo_shapes(op.split(", custom_call_target")[0])
    kv = next(s for s in shapes if s[0] == "s8" and len(s[1]) == 4)
    _, hkv, _, dh = kv[1]
    live = float(ctx["kv_live_tokens"])
    nbytes = live * hkv * (2 * dh + 2 * 2)
    q = next(s for s in shapes if s[0] == "bf16" and len(s[1]) == 4)
    heads = q[1][1]
    flops = 2.0 * 2.0 * live * heads * dh
    return flops, nbytes
