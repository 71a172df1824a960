"""The retention layer alone on the chip (``--tiny`` rehearses on the CPU).

    PYTHONPATH=/root/repo python tools/exp_retention.py [--tiny]

1. the single-token kernel against the chunk form of one token (the
   XLA lowering of the same step), some rows not live: outputs, state
   and normaliser compared on the device;
2. the kernel's time at the cell's geometry (20 rows x 8 KV heads of
   128, 5 query heads a KV head) with bfloat16 and with float32
   products, beside the bytes its walk moves over the chip's 819 GB/s;
3. one layer's chunk form at 1,024 and 512 tokens against a carried
   state: its time, and what the profiler calls the ops of the
   ``retention.*`` scopes (the benchmark's readers match them by name).
"""

import argparse
import glob
import json
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from mlcomp_tpu.models.retention import PowerRetention
from mlcomp_tpu.models.transformer import RopeSpec
from mlcomp_tpu.ops.pallas.retention import (
    expanded_width,
    retention_step,
    state_bytes_moved,
)


def timed(fn, *args, n=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    dh = 16 if args.tiny else 128
    hidden, heads, kv = (64, 4, 2) if args.tiny else (5120, 40, 8)
    rows = 4 if args.tiny else 20
    chunks = (16, 8) if args.tiny else (1024, 512)
    dtype = jnp.bfloat16
    g = heads // kv
    print("device", jax.devices()[0].device_kind, flush=True)

    layer = PowerRetention(hidden, heads, kv, dh, dtype,
                           rope=RopeSpec(base=1e6))
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (rows, chunks[0], hidden), dtype)
    pos = jnp.broadcast_to(jnp.arange(chunks[0]), (rows, chunks[0]))
    params = jax.jit(
        lambda: layer.init(key, x[:1, :8], pos[:1, :8])["params"])()
    params = jax.tree.map(
        lambda a: a.astype(dtype) if a.ndim > 1 and a.shape[-1] != kv else a,
        params)
    width = expanded_width(dh)

    def fresh(b):
        return {"state": jnp.zeros((b, kv, width, dh), jnp.float32),
                "norm": jnp.zeros((b, kv, width), jnp.float32),
                "cache_index": jnp.zeros((), jnp.int32)}

    @jax.jit
    def chunk(params, cache, xs, ps):
        out, upd = layer.apply({"params": params, "cache": cache}, xs, ps,
                               decode=True, mutable=["cache", "counters"])
        return out, upd["cache"]

    @jax.jit
    def step(params, cache, xs, ps, live):
        mask = jnp.broadcast_to(live[:, None], (xs.shape[0], 8))
        out, upd = layer.apply(
            {"params": params, "cache": cache}, xs, ps, decode=True,
            kv_mask=mask, cache_cursor=ps[:, 0],
            mutable=["cache", "counters"])
        return out, upd["cache"]

    # 1. kernel against the chunk form of one token, after a real prefix
    n0 = chunks[1]
    _, cache = chunk(params, fresh(rows), x[:, :n0], pos[:, :n0])
    live = jnp.arange(rows) % 3 != 1
    o_k, c_k = step(params, cache, x[:, n0:n0 + 1], pos[:, n0:n0 + 1], live)
    o_c, c_c = chunk(params, cache, x[:, n0:n0 + 1], pos[:, n0:n0 + 1])
    lv = np.asarray(live)
    rel = lambda a, b: float(  # noqa: E731
        jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))
    print("kernel_vs_chunk", json.dumps({
        "out_rel": rel(o_k[lv].astype(jnp.float32),
                       o_c[lv].astype(jnp.float32)),
        "state_rel": rel(c_k["state"][lv], c_c["state"][lv]),
        "norm_rel": rel(c_k["norm"][lv], c_c["norm"][lv]),
        "dead_rows_untouched": bool(
            jnp.all(c_k["state"][~lv] == cache["state"][~lv])),
    }), flush=True)

    # 2. the kernel alone at the cell's geometry
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (rows, kv, g, dh), dtype)
    k = jax.random.normal(ks[1], (rows, kv, dh), dtype)
    v = jax.random.normal(ks[2], (rows, kv, dh), dtype)
    lg = jnp.full((rows, kv), -0.01, jnp.float32)
    for product in ("bfloat16", "float32"):
        for n_live in (rows, rows // 2):
            alive = jnp.arange(rows) < n_live
            fn = jax.jit(
                lambda s, z, alive=alive, product=product: retention_step(
                    q, k, v, lg, alive, s, z, eps=1e-6,
                    product_dtype=product)[1:],
                donate_argnums=(0, 1))
            s, z = cache["state"] + 0, cache["norm"] + 0
            s, z = fn(s, z)
            jax.block_until_ready(s)
            t0 = time.perf_counter()
            for _ in range(10):
                s, z = fn(s, z)
            jax.block_until_ready(s)
            dt = (time.perf_counter() - t0) / 10
            moved = state_bytes_moved(n_live, kv, dh)
            print("kernel", json.dumps({
                "product": product, "live_rows": n_live, "ms": dt * 1e3,
                "GB_per_s": moved / dt / 1e9}), flush=True)

    # 3. the chunk form, and what the profiler calls its ops
    for c in chunks:
        cache1 = jax.tree.map(lambda a: a[:1] if a.ndim else a, cache)
        dt = timed(chunk, params, cache1, x[:1, :c], pos[:1, :c])
        print("chunk", json.dumps({"tokens": c, "ms": dt * 1e3}), flush=True)
    if not args.tiny:
        from benchmark.xplane import Trace

        with tempfile.TemporaryDirectory() as d:
            jax.profiler.start_trace(d)
            out = chunk(params, cache1, x[:1, :chunks[0]],
                        pos[:1, :chunks[0]])
            o_k, _ = step(params, cache, x[:, :1], pos[:, :1], live)
            jax.block_until_ready((out, o_k))
            jax.profiler.stop_trace()
            path = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                             recursive=True)[0]
            tr = Trace(path)
        names = {}
        for spans in tr.ops.values():
            for name, s, e in spans:
                t = names.setdefault(name, [0.0, 0])
                t[0] += (e - s) / 1e6
                t[1] += 1
        top = sorted(names.items(), key=lambda kv: -kv[1][0])[:25]
        for name, (ms, n) in top:
            print("op", round(ms, 3), n, name[:400], flush=True)
        print("ops_naming_a_scope",
              sum(1 for n in names if "retention." in n), "of", len(names))


if __name__ == "__main__":
    main()
