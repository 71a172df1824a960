"""qkv-shape (2048x6144) block_n sweep: the uniform n=512 rule gave 12
steps of 1MB (in-situ 18.3 us vs 15.6 at the old 1536 block).  Test
512/768/1024/1536 in one process; confirm (2048, 2048) keeps 512."""
import statistics
import time

import jax
import jax.numpy as jnp

from mlcomp_tpu.ops.pallas.quant_matmul import quant_matmul
from mlcomp_tpu.ops.quant import quantize_leaf

B, D = 8, 2048
key = jax.random.PRNGKey(0)


def qw(d_in, d_out, k):
    w = jax.random.normal(jax.random.fold_in(key, k), (d_in, d_out), jnp.float32)
    leaf = quantize_leaf(w)
    return leaf["q8"], leaf["q8_scale"].reshape(-1)


qk, qks = qw(D, 6144, 1)
sq, sqs = qw(D, D, 2)

CASES = {
    "qkv_n512": (qk, qks, 512),
    "qkv_n768": (qk, qks, 768),
    "qkv_n1024": (qk, qks, 1024),
    "qkv_n1536": (qk, qks, 1536),
    "sq_n512": (sq, sqs, 512),
    "sq_n1024": (sq, sqs, 1024),
}
N_LO, N_HI = 128, 1536


def looped(spec, n):
    w, s, bn = spec

    def f(x):
        y = quant_matmul(x, w, s, block_n=bn, block_d=2048)
        return (y[:, :D] * 1e-3).astype(jnp.bfloat16)

    return jax.jit(lambda x: jax.lax.fori_loop(0, n, lambda i, h: f(h), x))


x0 = jax.random.normal(jax.random.fold_in(key, 99), (B, D), jnp.bfloat16)
fns = {}
for nm, spec in CASES.items():
    for n in (N_LO, N_HI):
        fns[(nm, n)] = looped(spec, n)
for kk, fn in fns.items():
    t0 = time.perf_counter()
    float(fn(x0)[0, 0])
    print(f"  {kk}: {time.perf_counter()-t0:.1f}s", flush=True)

times = {k: [] for k in fns}
for _ in range(7):
    for kk, fn in fns.items():
        t0 = time.perf_counter()
        float(fn(x0)[0, 0])
        times[kk].append(time.perf_counter() - t0)

for nm, spec in CASES.items():
    t_lo = statistics.median(times[(nm, N_LO)])
    t_hi = statistics.median(times[(nm, N_HI)])
    per = (t_hi - t_lo) / (N_HI - N_LO) * 1e6
    roof = spec[0].size / 819e9 * 1e6
    print(f"{nm:12s}: {per:8.2f} us/call  roofline {roof:5.1f} "
          f"({roof/per*100 if per>0 else 0:5.1f}%)")

# RESULT (recorded for honesty): this sweep produced physically
# impossible readings (qkv_n768 at 111% of the HBM roofline, sq_n1024 at
# 223%) — the N_LO and N_HI loops are SEPARATE compiles, and
# differing kernel schedules between them can make the marginal
# difference meaningless at few-us signals.  Micro-sweeps are only
# trustworthy when the same pallas variant appears in both programs
# with consistent schedules; the end-to-end decode marginal (one scan
# program at two trip counts, stable across many sessions) is the
# arbiter for any default change.  The qkv n=512 default therefore
# stands on the e2e evidence (2184/2195 tok/s), not on this sweep.
