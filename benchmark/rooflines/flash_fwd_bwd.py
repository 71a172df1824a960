"""``ops/pallas/flash_attention.py``, causal, forward and backward.

Operations the algorithm needs, in units of one S x S x head_dim product
over all heads (2*B*H*S*S*D operations, halved by the causal mask): the
forward kernel 2 units (QK^T, PV); the backward 5 (the scores again, dV,
dP, dQ, dK), split evenly between the dq and the dkv kernel.  What the
two backward kernels recompute beyond that is not counted, so the share
errs low, never high.  Bound by operations at these shapes."""

from benchmark.xplane import hlo_shapes, nbytes


def match(op: str) -> bool:
    return op.startswith("%flash")


def cost(op: str, ctx):
    head = op.split(" = ")[0]
    shapes = hlo_shapes(op.split(", custom_call_target")[0])
    four = [s for s in shapes if s[0] in ("bf16", "f32") and len(s[1]) == 4]
    b, h, s, d = max(four, key=lambda t: t[1][1])[1]
    units = 2.0 if "fwd" in head else 2.5
    flops = units * 2.0 * b * h * s * s * d * 0.5
    return flops, float(sum(nbytes(t) for t in shapes[: 1 + len(four)]))
