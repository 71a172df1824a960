"""Profile ONE continuous-engine K-step dispatch (1.2B all-int8) and aggregate in-scan per-op device durations —
attributing the engine's ~9.0 ms marginal step vs the generate scan's
3.67 (round-5 finding: the host unpack loop measured FREE, so the gap
is device-side; this names the ops).  Same xplane methodology as
exp_profile_decode.py (device-stamped durations).  The figures above
are pre-round history; not measured on this chip."""
import collections
import glob
import os
import time
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np

from mlcomp_tpu.engine import DecodeEngine, _POISON
from mlcomp_tpu.models import create_model
from mlcomp_tpu.ops.quant import quantize_params
from mlcomp_tpu.train.state import init_model

LM_VOCAB, LM_HIDDEN, LM_LAYERS, LM_HEADS = 32768, 2048, 16, 16
DEC_PROMPT, DEC_NEW, K = 2048, 256, 8

cfg = {
    "name": "transformer_lm", "vocab_size": LM_VOCAB, "hidden": LM_HIDDEN,
    "layers": LM_LAYERS, "heads": LM_HEADS, "mlp_dim": 4 * LM_HIDDEN,
    "dtype": "bfloat16", "decode_fused": True, "kv_quant": True,
}
model = create_model(cfg)
gen = np.random.default_rng(2)
p128 = jnp.asarray(gen.integers(1, LM_VOCAB, size=(1, 128)), jnp.int32)
params, _ = init_model(model, {"x": p128}, jax.random.PRNGKey(0))
qvars = {"params": quantize_params(params)}
del params


def make_req():
    return {
        "ids": gen.integers(1, LM_VOCAB, size=DEC_PROMPT).tolist(),
        "n_new": DEC_NEW, "future": Future(), "temperature": 0.0,
        "top_k": LM_VOCAB, "top_p": 1.0, "eos_id": -1, "logprobs": False,
        "repetition_penalty": 1.0, "stream": None,
        "t_submit": time.perf_counter(),
    }


eng = DecodeEngine(model, qvars, slots=8, prompt_buckets=(DEC_PROMPT,),
                   max_new_cap=DEC_NEW, quant_kernel=True,
                   steps_per_dispatch=K)
eng._stop.set()
eng._queue.put(_POISON)
eng._thread.join(timeout=30)
for _ in range(8):
    eng._start_admission(make_req())
    while eng._adm is not None:
        eng._run_admission_chunk()
t0 = time.perf_counter()
eng._run_dispatch()
eng._run_dispatch()
print(f"warm {time.perf_counter()-t0:.0f}s", flush=True)

trace_dir = "/tmp/engine_trace"
os.system(f"rm -rf {trace_dir}")
with jax.profiler.trace(trace_dir):
    eng._run_dispatch()

pb = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
print("xplane files:", pb, flush=True)
# dependency-free reader (mlcomp_tpu/obs/devprof.py) — no TF install
# needed; same wire truth the tensorflow.tsl protobufs decoded
from mlcomp_tpu.obs.devprof import load_xspace, short_op as short

for plane in load_xspace(pb[0]):
    if "TPU" not in plane.name and "tpu" not in plane.name:
        continue
    print(f"\n=== plane: {plane.name} ===")
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        wh = [ev for ev in line.events if short(ev.name) == "while"]
        if not wh:
            print("no while span found")
            continue
        wh = max(wh, key=lambda e: e.duration_ps)
        lo, hi = wh.offset_ps, wh.offset_ps + wh.duration_ps
        print(f"K-step scan span: {wh.duration_ps/1e9:.2f} ms "
              f"(/{K} steps = {wh.duration_ps/1e9/K:.3f} ms/step)")
        total = collections.Counter()
        counts = collections.Counter()
        for ev in line.events:
            if ev.name == wh.name:
                continue
            if not (lo <= ev.offset_ps < hi):
                continue
            total[short(ev.name)] += ev.duration_ps / 1e6  # us
            counts[short(ev.name)] += 1
        grand = sum(total.values())
        print(f"in-scan op total: {grand/1e3:.2f} ms "
              f"({grand/1e3/K:.3f} ms/step if no overlap)")
        for nm, us in total.most_common(30):
            print(f"  {us/K:8.1f} us/step  x{counts[nm]/K:6.1f}  {nm}")

# Host-side pipeline A/B: the same dispatch driven synchronous
# (issue + resolve) vs double-buffered (issue N+1 before resolving N).
# The device per-op durations above are depth-invariant; the wall
# delta here is purely the host dispatch overhead the in-flight
# pipeline hides.
walls = {1: [], 2: []}
for _ in range(3):
    t0 = time.perf_counter()
    eng._run_dispatch()
    walls[1].append(time.perf_counter() - t0)
    eng._issue_dispatch()  # prime outside the clock
    t0 = time.perf_counter()
    eng._issue_dispatch()
    eng._process_oldest()
    walls[2].append(time.perf_counter() - t0)
    while eng._inflight:
        eng._process_oldest()
d1, d2 = (1e3 * min(walls[k]) for k in (1, 2))
print(f"\npipeline A/B (host wall per dispatch, best of 3): "
      f"depth1 {d1:.1f} ms, depth2 {d2:.1f} ms, "
      f"hidden {max(d1 - d2, 0.0):.1f} ms")
