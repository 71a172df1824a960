"""The grouped matmul's tile sweep on the chip (PYTHONPATH=/root/repo).

The expert layer's three products (SwiGLU front half, down projection)
at the served widths, over the experts one chip holds, at the decode
step's and the prefill chunk's assignment counts (48 x 10 and 256 x 10),
routing uniform over the published experts.  Per row tile and weight
block: microseconds a layer call, and the bytes of the experts touched
over that time against the chip's 819 GB/s.  ``--megablox 1`` adds
``jax.experimental.pallas.ops.tpu.megablox.gmm`` on the same sorted
rows for comparison.  Prints one JSON line a setting.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from mlcomp_tpu.ops.pallas.grouped_matmul import group_layout, grouped_matmul

HBM = 819e9


def layer_fn(tm, block_up, block_down, held):
    def fn(x, group, w_gate, w_up, w_down):
        a = group.shape[0]
        lay = group_layout(group, held, tm,
                           source=jnp.arange(a, dtype=jnp.int32) // 10)
        rows = jnp.take(x, lay.row_source, axis=0)
        act = grouped_matmul(rows, w_gate, lay.tile_group, lay.tiles_used,
                             w2=w_up, block_n=block_up)
        out = grouped_matmul(act, w_down, lay.tile_group, lay.tiles_used,
                             block_n=block_down)
        return out, lay.sizes

    return jax.jit(fn)


def megablox_fn(held):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    def fn(x, group, w_gate, w_up, w_down):
        a = group.shape[0]
        keep = group < held
        order = jnp.argsort(jnp.where(keep, group, held), stable=True)
        rows = jnp.take(x, order // 10, axis=0)
        sizes = jnp.bincount(jnp.where(keep, group, held),
                             length=held + 1)[:held].astype(jnp.int32)
        mm = lambda l, r: gmm(l, r, sizes, tiling=(128, 1024, 512))  # noqa
        act = jax.nn.silu(mm(rows, w_gate)) * mm(rows, w_up)
        return mm(act.astype(x.dtype), w_down), sizes

    return jax.jit(fn)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--hidden", type=int, default=3072)
    ap.add_argument("--width", type=int, default=1024)
    ap.add_argument("--experts", type=int, default=256)
    ap.add_argument("--held", type=int, default=128)
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--megablox", type=int, default=0)
    args = ap.parse_args()
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform}),
          flush=True)
    h, f, e = args.hidden, args.width, args.held
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    w_gate = (jax.random.normal(ks[0], (e, h, f)) * h ** -0.5).astype(jnp.bfloat16)
    w_up = (jax.random.normal(ks[1], (e, h, f)) * h ** -0.5).astype(jnp.bfloat16)
    w_down = (jax.random.normal(ks[2], (e, f, h)) * f ** -0.5).astype(jnp.bfloat16)
    rng = np.random.default_rng(0)
    for tokens in (48, 256):
        x = jax.random.normal(ks[3], (tokens, h)).astype(jnp.bfloat16)
        # 10 distinct experts a token, uniform over the published ones
        group = np.stack([
            rng.permutation(args.experts)[:10] for _ in range(tokens)
        ]).reshape(-1).astype(np.int32)
        group = jnp.asarray(np.where(group < e, group, e))
        settings = [
            ("own", tm, bu, bd)
            for tm in (16, 32, 64)
            for bu, bd in ((256, 1024), (128, 512), (512, 1536))
        ]
        if args.megablox:
            settings.append(("megablox", 128, 512, 512))
        for kind, tm, bu, bd in settings:
            try:
                fn = (layer_fn(tm, bu, bd, e) if kind == "own"
                      else megablox_fn(e))
                out, sizes = fn(x, group, w_gate, w_up, w_down)
                jax.block_until_ready(out)
                t0 = time.perf_counter()
                for _ in range(args.reps):
                    out, sizes = fn(x, group, w_gate, w_up, w_down)
                jax.block_until_ready(out)
                us = (time.perf_counter() - t0) / args.reps * 1e6
            except Exception as err:  # a tile the compiler refuses
                print(json.dumps({"tokens": tokens, "kind": kind, "tm": tm,
                                  "block_up": bu, "block_down": bd,
                                  "refused": str(err)[:300]}), flush=True)
                continue
            touched = int(np.sum(np.asarray(sizes) > 0))
            nbytes = touched * 3 * h * f * 2
            print(json.dumps({
                "tokens": tokens, "kind": kind, "tm": tm, "block_up": bu,
                "block_down": bd, "us_per_layer_call": round(us, 1),
                "experts_touched": touched,
                "weight_bytes_share_of_hbm_peak": round(
                    nbytes / HBM / (us * 1e-6), 3),
            }), flush=True)


if __name__ == "__main__":
    main()
