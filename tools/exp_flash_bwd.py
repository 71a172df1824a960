"""The causal flash backward alone on the chip: the fused kernel
(``flash_dq_dkv_kernel_tri``) beside the two kernels it replaces
(``flash_dq_kernel_tri`` + ``flash_dkv_kernel_tri``, what
``_flash_bwd_tri`` still runs over ``DQ_RESIDENT_BUDGET``), at
``train-4k``'s shape (B 2, S 4096, H 16 over 8 KV heads, D 128) and at
S = 2048 / 8192 with the same 8,192 tokens (8192 is over the budget:
only the two kernels run there).

    PYTHONPATH=/root/repo python tools/exp_flash_bwd.py [--seqs 4096,2048,8192]

Each shape first checks on this device that the two ways return the
same dq, dk and dv, bit for bit (exit 1 if not), then runs each
``--calls`` times under the profiler and reads the kernels' own device
time by name (``benchmark.xplane``): ms a call, and the share of the
bf16 peak on the products the way multiplies (7 for the two kernels, 5
fused) and on the 5 the algorithm needs.  ``--tiny`` rehearses on the
CPU in interpret mode (equality only: a CPU run gives no time).
"""
import argparse
import glob
import json
import os
import sys
import tempfile
from unittest import mock

import jax
import jax.numpy as jnp

from mlcomp_tpu.ops.pallas import flash_attention as fa

H, H_KV, D, TOKENS = 16, 8, 128, 8192


def backward(two_kernels, block, interpret):
    """Jitted ``_flash_bwd_tri``; traced with the budget at nothing, it
    takes the two-kernel way whatever the shape."""
    def fn(q, k, v, do, lse, delta):
        budget = 0 if two_kernels else fa.DQ_RESIDENT_BUDGET
        with mock.patch.object(fa, "DQ_RESIDENT_BUDGET", budget):
            return fa._flash_bwd_tri(
                D ** -0.5, block, block, interpret, q, k, v, do, lse, delta
            )[:3]
    return jax.jit(fn)


def kernel_ms(run, calls):
    """Device ms a call of every ``%flash*`` op ``run`` launches."""
    from benchmark.xplane import Trace

    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(calls):
            out = run()
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        tr = Trace(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                             recursive=True)[0])
    ms = {}
    for name, s, e in tr.kernel_events(lambda op: op.startswith("%flash")):
        head = name.split(" = ")[0].lstrip("%").split(".")[0]
        ms[head] = ms.get(head, 0.0) + (e - s) / 1e6 / calls
    return ms


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="4096,2048,8192")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    seqs = [256] if args.tiny else [int(s) for s in args.seqs.split(",")]
    tokens = 512 if args.tiny else TOKENS
    dev = jax.devices()[0]
    peak = None
    if not args.tiny:
        from benchmark.device import PEAKS
        peak = PEAKS[dev.device_kind]["bf16_flops"]
    print(json.dumps({"device": dev.device_kind, "bf16_peak": peak}),
          flush=True)

    same = True
    for s in seqs:
        b = max(1, tokens // s)
        block = fa._pick_block(s, preferred=128 if args.tiny else 1024)
        ks = jax.random.split(jax.random.PRNGKey(s), 4)
        q, do = (jax.random.normal(k_, (b, H, s, D), jnp.bfloat16)
                 for k_ in ks[:2])
        k, v = (jax.random.normal(k_, (b, H_KV, s, D), jnp.bfloat16)
                for k_ in ks[2:])
        out, lse = jax.jit(lambda q, k, v: fa._flash_fwd(
            q, k, v, None, None, D ** -0.5, True, block, block, args.tiny
        ))(q, k, v)
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), -1)
        delta = jnp.broadcast_to(delta[..., None], (*delta.shape, fa.LANES))
        operands = (q, k, v, do, lse, delta)
        resident = H // H_KV * s * D * 4
        ways = {"two": backward(True, block, args.tiny)}
        if resident <= fa.DQ_RESIDENT_BUDGET:
            ways["fused"] = backward(False, block, args.tiny)
        grads = [fn(*operands) for fn in ways.values()]
        equal = all(bool(jnp.array_equal(a, b_))
                    for a, b_ in zip(grads[0], grads[-1]))
        same &= equal
        nq = s // block
        # one product: 2 * B * H * (live block pairs) * block^2 * D
        product = 2.0 * b * H * (nq * (nq + 1) // 2) * block * block * D
        row = {"B": b, "S": s, "block": block, "ways": list(ways),
               "bit_equal": equal, "dq_resident_MiB": resident / 2 ** 20}
        if not args.tiny:
            for w, fn in ways.items():
                ms = kernel_ms(lambda fn=fn: fn(*operands), args.calls)
                total = sum(ms.values())
                done = 5 if w == "fused" else 7
                row[w] = {
                    "kernels_ms": ms, "ms": total,
                    f"peak_share_on_{done}": done * product / (total / 1e3) / peak,
                    "peak_share_on_5_needed": 5 * product / (total / 1e3) / peak,
                }
        print(json.dumps(row), flush=True)
    sys.exit(0 if same else 1)


if __name__ == "__main__":
    main()
