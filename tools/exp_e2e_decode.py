"""End-to-end check of round-4 decode work: b8_kv8_int8 (fused layout +
auto blocks) vs its roofline, plus b8_kv8 for reference, by the
marginal protocol (the wall of N more steps)."""
import os
import statistics
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from mlcomp_tpu.models import create_model
from mlcomp_tpu.models.generation import generate
from mlcomp_tpu.ops.quant import quantize_params
from mlcomp_tpu.train.state import init_model

LM_VOCAB, LM_HIDDEN, LM_LAYERS, LM_HEADS = 32768, 2048, 16, 16
DEC_PROMPT, DEC_NEW = 2048, 256
V5E_HBM_BW = 819e9

lm_cfg = {
    "name": "transformer_lm", "vocab_size": LM_VOCAB, "hidden": LM_HIDDEN,
    "layers": LM_LAYERS, "heads": LM_HEADS, "mlp_dim": 4 * LM_HIDDEN,
    "dtype": "bfloat16", "decode_fused": True, "kv_quant": True,
}
model_kv8 = create_model(lm_cfg)
gen = np.random.default_rng(2)
prompt = jnp.asarray(gen.integers(1, LM_VOCAB, size=(8, DEC_PROMPT)), jnp.int32)
params, _ = init_model(model_kv8, {"x": prompt[:1, :128]}, jax.random.PRNGKey(0))
qvars = {"params": quantize_params(params)}
del params

modes = {"kv8_int8": True, "kv8": False}
fns = {}
for mode, qk in modes.items():
    for n_new in (DEC_NEW // 2, DEC_NEW):
        fns[(mode, n_new)] = jax.jit(
            partial(generate, model_kv8, max_new_tokens=n_new, quant_kernel=qk)
        )
for kk, fn in fns.items():
    t0 = time.perf_counter()
    int(fn(qvars, prompt)[0, -1])
    print(f"  {kk}: compiled {time.perf_counter()-t0:.0f}s", flush=True)

times = {k: [] for k in fns}
for _ in range(5):
    for kk, fn in fns.items():
        t0 = time.perf_counter()
        int(fn(qvars, prompt)[0, -1])
        times[kk].append(time.perf_counter() - t0)

d = LM_HIDDEN
weight_bytes_bf16 = sum(
    int(np.prod(s)) for s in [
        *[(d, d)] * 4 * LM_LAYERS,
        *[(d, 4 * d)] * 3 * LM_LAYERS,
        (d, LM_VOCAB),
    ]
) * 2
kv_bytes_int8 = (DEC_PROMPT + DEC_NEW) * LM_LAYERS * 2 * (d + 4 * LM_HEADS)
for mode in modes:
    dt = (statistics.median(times[(mode, DEC_NEW)])
          - statistics.median(times[(mode, DEC_NEW // 2)]))
    n_tok = 8 * (DEC_NEW // 2)
    w = weight_bytes_bf16 * (0.5 if mode.endswith("int8") else 1.0)
    roof = 8 * V5E_HBM_BW / (w + 8 * kv_bytes_int8)
    tps = n_tok / dt
    print(f"b8_{mode}: {tps:.1f} tok/s  roofline {roof:.1f}  "
          f"({tps/roof*100:.1f}%)  ms/tok/seq {dt/n_tok*8*1e3:.3f}")
