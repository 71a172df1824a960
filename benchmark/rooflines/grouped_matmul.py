"""``ops/pallas/grouped_matmul.py``: rows sorted by expert, each tile
against its own expert's matrix; with a second weight stack the SwiGLU
front half (two products, one read of the rows).

The buffers the kernel is handed are padded: rows for the worst split
over the experts, and every expert's weights.  What the algorithm needs
is the rows that hold an assignment and the weights of the experts a
token reached.  Both come from the program's own counters over the run
(``ctx["moe_rows_per_call"]``: assignments held a call;
``ctx["moe_experts_per_call"]``: experts touched a call, never more than
the experts the op was handed), the widths from the op's HLO shapes:

- operations: 2 * rows * K * N a weight stack;
- bytes: the rows read and written once (rows * (K + N) elements), and
  K * N elements a stack for every expert TOUCHED, not for all held: a
  kernel that skips the experts no token reached must not read over
  100%.

Decode calls (a few rows an expert) are bound by the weights' bytes."""

from benchmark.xplane import HLO_TYPES, hlo_shapes


def match(op: str) -> bool:
    return op.split(" = ")[0].startswith("%grouped_matmul")


def cost(op: str, ctx):
    shapes = hlo_shapes(op.split(", custom_call_target")[0])
    stacks = [s for s in shapes[1:] if len(s[1]) == 3]
    dtype, (experts, k, n) = stacks[0]
    size = HLO_TYPES[dtype]
    rows = float(ctx["moe_rows_per_call"])
    touched = min(float(ctx["moe_experts_per_call"]), float(experts))
    flops = 2.0 * rows * k * n * len(stacks)
    nbytes = size * (rows * (k + n) + touched * k * n * len(stacks))
    return flops, nbytes
