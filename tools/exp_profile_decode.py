"""Profile the b8_kv8_int8 decode step: capture a device trace of the
token loop and aggregate per-kernel durations, so the remaining
roofline gap is attributed, not guessed.  (Per-kernel durations are
stamped by the device-side tracer, so they hold whatever the host's
wall clock saw.)"""
import collections
import glob
import os
import sys
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
N_NEW = 16

cfg = {
    "name": "transformer_lm", "vocab_size": LM_VOCAB, "hidden": LM_HIDDEN,
    "layers": LM_LAYERS, "heads": LM_HEADS, "mlp_dim": 4 * LM_HIDDEN,
    "dtype": "bfloat16", "decode_fused": True, "kv_quant": True,
}
model = create_model(cfg)
gen = np.random.default_rng(2)
prompt = jnp.asarray(gen.integers(1, LM_VOCAB, size=(8, 2048)), jnp.int32)
params, _ = init_model(model, {"x": prompt[:1, :128]}, jax.random.PRNGKey(0))
qvars = {"params": quantize_params(params)}
del params

fn = jax.jit(partial(generate, model, max_new_tokens=N_NEW, quant_kernel=True))
t0 = time.perf_counter()
int(fn(qvars, prompt)[0, -1])
print(f"compiled {time.perf_counter()-t0:.0f}s", flush=True)

trace_dir = "/tmp/decode_trace"
os.system(f"rm -rf {trace_dir}")
with jax.profiler.trace(trace_dir):
    int(fn(qvars, prompt)[0, -1])

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
        # locate the token-loop while span; aggregate only events inside
        wh = [ev for ev in line.events if short(ev.name) == "while"]
        if not wh:
            print("no while span found")
            continue
        wh = max(wh, key=lambda e: e.duration_ps)
        lo, hi = wh.offset_ps, wh.offset_ps + wh.duration_ps
        print(f"while span: {wh.duration_ps/1e9:.2f} ms "
              f"(/{N_NEW - 1} steps = {wh.duration_ps/1e9/(N_NEW-1):.3f})")
        total = collections.Counter()
        counts = collections.Counter()
        for ev in line.events:
            if ev.name == wh.name:
                continue
            if not (lo <= ev.offset_ps and ev.offset_ps < hi):
                continue
            total[short(ev.name)] += ev.duration_ps / 1e6  # us
            counts[short(ev.name)] += 1
        grand = sum(total.values())
        steps = N_NEW - 1
        print(f"in-while op total: {grand/1e3:.2f} ms "
              f"({grand/1e3/steps:.3f} ms/step if no overlap)")
        for nm, us in total.most_common(30):
            print(f"  {us/steps:8.1f} us/step  x{counts[nm]/steps:6.1f}  {nm}")
        # break copies/DUS down by result shape to find the producers
        shp = collections.Counter()
        scount = collections.Counter()
        for ev in line.events:
            nm = ev.name
            key = short(nm)
            if key not in ("copy", "dynamic_update_slice", "broadcast_in_dim"):
                continue
            if not (lo <= ev.offset_ps < hi):
                continue
            sig = key + "  " + nm.split(" = ")[1].split("(")[0][:70]
            shp[sig] += ev.duration_ps / 1e6
            scount[sig] += 1
        print("\ncopy/DUS by shape:")
        for sig, us in shp.most_common(14):
            print(f"  {us/steps:8.1f} us/step  x{scount[sig]/steps:6.1f}  {sig}")
