"""``ops/pallas/kda.py``: one token a live row against the row's
delta-rule states, in place.

What the mathematics needs, whatever implements it (NOT the op's
shapes: a state padded or laid out wider does the same work in more
bytes and must read a LOWER share, never one above 100%).  Per live row
and head, with the state ``key x value`` (128 x 128):

- bytes: the state, float32, read once and written once; the step's q,
  k, v (bfloat16), its decay a key channel and its beta (float32), and
  the output (float32);
- operations: the decay (1), what the state says of k (2), the rank-one
  write (2) and the readout (2), each over ``key x value``.

Live rows a call are the program's own count over the run
(``ctx["kda_rows_per_call"]``).  The step is bound by bytes."""


def match(op: str) -> bool:
    return op.split(" = ")[0].startswith("%kda_step")


def cost(op: str, ctx):
    d = ctx["kda_dims"]
    rows = float(ctx["kda_rows_per_call"])
    heads, dh = d["kda_heads"], d["kda_dim"]
    state = dh * dh * 4 * 2
    step = 3 * dh * 2 + dh * 4 + 4 + dh * 4
    return rows * heads * 7.0 * dh * dh, rows * heads * (state + step)
