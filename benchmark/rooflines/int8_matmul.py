"""``ops/pallas/quant_matmul.py``: y = x @ dequant(w8) * scale.

Operations and bytes from the shapes in the op's own HLO text: 2*M*K*N
operations (the kernel feeds the MXU bfloat16), and every operand and
the result read or written once.  Decode calls (M <= 64 rows) are bound
by the int8 weight's bytes, prefill chunks by operations."""

from benchmark.xplane import hlo_shapes, nbytes


def match(op: str) -> bool:
    return op.startswith("%quant_matmul")


def cost(op: str, ctx):
    shapes = hlo_shapes(op.split(", custom_call_target")[0])
    w = next(s for s in shapes if s[0] == "s8" and len(s[1]) == 2)
    k, n = w[1]
    x = next(s for s in shapes[1:] if s[0] != "s8" and len(s[1]) == 2
             and s[1][1] == k)
    return 2.0 * x[1][0] * k * n, float(sum(nbytes(s) for s in shapes))
