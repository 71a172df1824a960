"""``ops/pallas/retention.py``: one token a live row against the row's
recurrent state, in place.

What the mathematics needs, whatever implements it (NOT the op's
shapes: a state padded or laid out wider does the same work in more
bytes and must read a LOWER share, never one above 100%).  With ``D``
the symmetric second power of a head (``head_dim * (head_dim + 1) / 2``:
8,256 at 128, ``dims_of``'s ``expanded``), per live row:

- bytes: every KV head's state ``D x head_dim`` and normaliser ``D``,
  float32, read once and written once; the step's q, k, v (bfloat16),
  gates and outputs (float32);
- operations: a decay-and-add (2) for each KV head and a
  multiply-and-add (2) for each query head, over ``D x head_dim``.

Live rows a call are the program's own count over the run
(``ctx["retention_rows_per_call"]``).  The step is bound by bytes."""


def match(op: str) -> bool:
    return op.split(" = ")[0].startswith("%retention_step")


def cost(op: str, ctx):
    d = ctx["retention_dims"]
    rows = float(ctx["retention_rows_per_call"])
    heads, kv, dh, width = d["heads"], d["kv_heads"], d["head_dim"], d["expanded"]
    state = kv * (width * dh + width) * 4 * 2
    step = 2 * (heads + 2 * kv) * dh + 4 * (kv + heads * dh)
    flops = (2.0 * kv + 2.0 * heads) * width * dh
    return rows * flops, rows * (state + step)
