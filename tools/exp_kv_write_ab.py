"""ONE-process A/B of the int8 KV cache's single-token update layout:
{reshape, transpose} x {where, dus} scale writes, on the full 1.2B
b8_kv8_int8 decode (marginal 128-vs-256-token timing, interleaved,
median of 5).  All four variants in one process, so they share one
compile session; not yet run on this chip."""
import itertools
import statistics
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import mlcomp_tpu.models.transformer as tr
from mlcomp_tpu.models import create_model
from mlcomp_tpu.models.generation import generate
from mlcomp_tpu.ops.quant import quantize_params
from mlcomp_tpu.train.state import init_model

LM_VOCAB, LM_HIDDEN, LM_LAYERS, LM_HEADS = 32768, 2048, 16, 16
DEC_PROMPT, DEC_NEW = 2048, 256

cfg = {
    "name": "transformer_lm", "vocab_size": LM_VOCAB, "hidden": LM_HIDDEN,
    "layers": LM_LAYERS, "heads": LM_HEADS, "mlp_dim": 4 * LM_HIDDEN,
    "dtype": "bfloat16", "decode_fused": True, "kv_quant": True,
}
model = create_model(cfg)
gen = np.random.default_rng(2)
prompt = jnp.asarray(gen.integers(1, LM_VOCAB, size=(8, DEC_PROMPT)), jnp.int32)
params, _ = init_model(model, {"x": prompt[:1, :128]}, jax.random.PRNGKey(0))
qvars = {"params": quantize_params(params)}
del params

fns = {}
for reshape, sw in itertools.product((True, False), ("where", "dus")):
    tr._KV_UPDATE_RESHAPE = reshape
    tr._KV_SCALE_WRITE = sw
    for n_new in (DEC_NEW // 2, DEC_NEW):
        key = (reshape, sw, n_new)
        fns[key] = jax.jit(
            partial(generate, model, max_new_tokens=n_new, quant_kernel=True)
        )
        t0 = time.perf_counter()
        int(fns[key](qvars, prompt)[0, -1])
        print(f"  {key}: compiled {time.perf_counter()-t0:.0f}s", flush=True)

times = {k: [] for k in fns}
for _ in range(5):
    for kk, fn in fns.items():
        t0 = time.perf_counter()
        int(fn(qvars, prompt)[0, -1])
        times[kk].append(time.perf_counter() - t0)

for reshape, sw in itertools.product((True, False), ("where", "dus")):
    dt = (statistics.median(times[(reshape, sw, DEC_NEW)])
          - statistics.median(times[(reshape, sw, DEC_NEW // 2)]))
    ms = dt / (DEC_NEW // 2) * 1e3
    tps = 8 * (DEC_NEW // 2) / dt
    print(f"reshape={reshape!s:5s} scale={sw:5s}: {ms:6.3f} ms/step  "
          f"{tps:7.1f} tok/s")
