"""decode_attention granule sweep on the chip, at the serve cells'
geometry (B 48, H 16, Hkv 8, dh 128, L 2560) over window sets shaped
like the two cells' traffic, plus the 1.2B smoke's full buffer (B 8,
Hkv 16, L 2304).  Marginal timing: one jitted ``fori_loop`` a variant
with a traced trip count, run at two counts; us a call is the slope.

    python tools/exp_decattn.py [--parent DIR] [--out FILE] [--append]

``--parent`` names a checkout whose ``decode_attention`` is timed
beside this tree's, on the windows that tree's engine would hand it
(a retired row keeps its stale window) and on this tree's (empty).

``--append`` runs the KV-append cases instead of the granule sweep (the
table that chose PR 29's form): a layer's write of one token a row
plus its attention, at 5 / 10 / 48 live rows of 48 and contexts of
~240 / ~700 tokens, three ways: ``attend`` (the plain kernel, nothing
written), ``loop_write`` (``_row_cursor_dus`` for K and V over every
row, a select over each scale cache, then the plain kernel: the write
up to PR 28) and ``append`` (the kernel's ``append``).  The caches ride
the loop's carry, donated, as they ride the engine's K-step scan; each
case first checks on this device that ``append`` leaves the bytes
``loop_write`` leaves in every live row and returns its output.
"""
import argparse
import functools
import importlib.util
import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from mlcomp_tpu.ops.pallas.decode_attention import decode_attention

N_LO, N_HI, REPEATS = 64, 448, 5
BUCKETS = (256, 512, 1024, 2048)


def windows(case, b, l_buf, rng):
    """(start, stop, live) per row: a prompt of p tokens is left-padded
    to its bucket and n tokens have been decoded behind it."""
    def row(p, n):
        bucket = next(x for x in BUCKETS if x >= p)
        return bucket - p, min(bucket + n, l_buf)

    def chat():
        p = int(np.clip(rng.lognormal(np.log(384), 0.8), 32, 2048))
        return row(p, int(rng.integers(0, 96)))

    def offline():
        p = int(np.clip(rng.lognormal(np.log(96), 0.6), 16, 256))
        return row(p, int(rng.integers(0, 224)))

    if case in ("steady", "steady_long"):
        # 10 live rows; the rest retired (a stale window) or never used
        live = np.zeros(b, bool)
        live[rng.choice(b, 10, replace=False)] = True
        win = [chat() if live[i] or i % 5 else (0, 1) for i in range(b)]
        if case == "steady_long":   # with a 2048-token prompt in it
            win[int(np.flatnonzero(live)[0])] = (0, 2048 + 128)
    elif case == "offline":
        live = np.ones(b, bool)
        win = [offline() for _ in range(b)]
    elif case == "full":
        live = np.ones(b, bool)
        win = [(0, l_buf)] * b
    else:
        raise ValueError(case)
    start, stop = (np.array(x, np.int32) for x in zip(*win))
    return start, stop, live


def looped(fn, start, stop, **kw):
    """``(q, operands, n)`` -> q after n calls.  The cache rides as an
    argument: closed over, it is a constant of the program, and the
    first sweep spent most of 827 s compiling 760 MB executables."""
    start, stop = jnp.asarray(start), jnp.asarray(stop)

    def run(q, operands, n):
        def body(i, q):
            o = fn(q, *operands, kv_start=start, kv_stop=stop, **kw)
            return (o * 1e-3 + q * 0.5).astype(q.dtype)

        return jax.lax.fori_loop(0, n, body, q)

    return jax.jit(run)


def slope_us(call):
    """us an iteration of ``call(n)``, which runs n and waits: the
    slope between two trip counts."""
    call(2)                                      # compile
    t = {n: [] for n in (N_LO, N_HI)}
    for _ in range(REPEATS):
        for n in t:
            t0 = time.perf_counter()
            call(n)
            t[n].append(time.perf_counter() - t0)
    lo, hi = (statistics.median(t[n]) for n in (N_LO, N_HI))
    return (hi - lo) / (N_HI - N_LO) * 1e6


def us_a_call(fn, q, operands):
    return slope_us(lambda n: float(fn(q, operands, n)[0, 0, 0]))


def layer_step(variant, q, caches, new, start, cur):
    """One layer's write of a token a row and its attention."""
    from mlcomp_tpu.models.transformer import _row_cursor_dus

    k8, ks, v8, vs = caches
    kq, ks_new, vq, vs_new = new
    kw = dict(kv_start=start, kv_stop=cur + 1)
    if variant == "append":
        out, *caches = decode_attention(q, *caches, append=new, **kw)
        return out, tuple(caches)
    if variant == "loop_write":
        b, h_kv, l_buf = ks.shape[0], ks.shape[1], ks.shape[3]
        hit = (
            jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, l_buf), 3)
            == cur[:, None, None, None]
        )
        caches = (
            _row_cursor_dus(k8, kq[:, :, None, :], cur, 2),
            jnp.where(hit, ks_new.reshape(b, h_kv, 1, 1).astype(ks.dtype), ks),
            _row_cursor_dus(v8, vq[:, :, None, :], cur, 2),
            jnp.where(hit, vs_new.reshape(b, h_kv, 1, 1).astype(vs.dtype), vs),
        )
    return decode_attention(q, *caches, **kw), caches


def looped_layer(variant, new, start, cur):
    """``(q, caches, n)`` -> (q, caches) after n layer steps, the
    caches the loop's carry and donated."""
    def run(q, caches, n):
        def body(i, carry):
            q, caches = carry
            o, caches = layer_step(variant, q, caches, new, start, cur)
            return (o * 1e-3 + q * 0.5).astype(q.dtype), caches

        return jax.lax.fori_loop(0, n, body, (q, caches))

    return jax.jit(run, donate_argnums=(1,))


def us_a_layer(fn, q, caches):
    """(us a layer step, the caches as the last call left them): each
    call donates the caches and takes the next call's from its result."""
    held = [caches]

    def call(n):
        out, held[0] = fn(q, held[0], n)
        float(out[0, 0, 0])

    return slope_us(call), held[0]


def append_cases(args, dev):
    """The table behind PR 29's choice of form (module docstring)."""
    b, h, hkv, l_buf, dh = 48, 16, 8, 2560, 128
    if args.tiny:
        b, h, hkv = 12, 4, 2
    key = jax.random.PRNGKey(29)

    kv = [jax.random.randint(jax.random.fold_in(key, i),
                             (b, hkv, l_buf, dh), -127, 127, jnp.int8)
          for i in (0, 1)]
    sc = [(jax.random.uniform(jax.random.fold_in(key, i),
                              (b, hkv, 1, l_buf)) * 0.01
           ).astype(jnp.bfloat16) for i in (2, 3)]
    caches = (kv[0], sc[0], kv[1], sc[1])
    q = jax.random.normal(jax.random.fold_in(key, 9), (b, h, dh),
                          jnp.bfloat16)
    new = (
        jax.random.randint(jax.random.fold_in(key, 4), (b, hkv, dh),
                           -127, 127, jnp.int8),
        jax.random.uniform(jax.random.fold_in(key, 5), (b, hkv)) * 0.01,
        jax.random.randint(jax.random.fold_in(key, 6), (b, hkv, dh),
                           -127, 127, jnp.int8),
        jax.random.uniform(jax.random.fold_in(key, 7), (b, hkv)) * 0.01,
    )
    results = []
    rng = np.random.default_rng(29)
    for context in (240, 700):
        for live_rows in ((5, 10, b) if not args.tiny else (3, b)):
            live = np.zeros(b, bool)
            live[rng.choice(b, live_rows, replace=False)] = True
            bucket = next(x for x in BUCKETS if x >= context - 40)
            # a prompt of context - 40 tokens left-padded to its
            # bucket, 40 + row tokens decoded behind it
            cur = np.where(live, bucket + 39 + np.arange(b) % 7, 0)
            start = np.where(live, bucket - (context - 40), l_buf)
            start, cur = (jnp.asarray(x, jnp.int32) for x in (start, cur))
            wrote = {
                v: jax.jit(functools.partial(layer_step, v))(
                    q, caches, new, start, cur
                ) for v in ("loop_write", "append")
            }
            same = all(
                bool(jnp.array_equal(x[live], y[live]))
                for x, y in zip(jax.tree.leaves(wrote["append"]),
                                jax.tree.leaves(wrote["loop_write"]))
            )
            del wrote
            print(f"context {context} live {live_rows}: append == "
                  f"loop_write in live rows: {same}", flush=True)
            for variant in ("attend", "loop_write", "append"):
                us, caches = us_a_layer(
                    looped_layer(variant, new, start, cur), q, caches
                )
                results.append({
                    "context": context, "live_rows": live_rows,
                    "variant": variant, "us_a_layer_call": us,
                    "bit_equal": same,
                })
                print(f"context {context:4d} live {live_rows:2d} "
                      f"{variant:10s} {us:8.2f} us a layer call", flush=True)
    with open(args.out, "w") as f:
        json.dump({"device": dev.device_kind, "results": results}, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent")
    ap.add_argument("--out", default="chiprun_out/decattn_sweep.json")
    ap.add_argument("--tiny", action="store_true",
                    help="a CPU rehearsal of the control flow: no timing")
    ap.add_argument("--append", action="store_true",
                    help="the KV-append cases instead of the granule sweep")
    args = ap.parse_args()
    if args.tiny:
        global N_LO, N_HI, REPEATS
        N_LO, N_HI, REPEATS = 1, 2, 1
    parent = None
    if args.parent:
        spec = importlib.util.spec_from_file_location(
            "parent_decode_attention",
            f"{args.parent}/mlcomp_tpu/ops/pallas/decode_attention.py",
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        parent = mod.decode_attention

    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    if args.append:
        if args.out == ap.get_default("out"):
            args.out = "chiprun_out/decattn_append.json"
        return append_cases(args, dev)
    results = []
    geometries = [
        ("cell", 48, 16, 8, 2560, ("steady", "steady_long", "offline", "full"),
         (128, 256, 512, 640)),
        ("smoke", 8, 16, 16, 2304, ("full",), (128, 256, 384, 768)),
    ]
    if args.tiny:
        geometries = [("tiny", 12, 4, 2, 2560, ("steady", "offline"), (128,))]
    for name, b, h, hkv, l_buf, cases, granules in geometries:
        dh = 128
        key = jax.random.PRNGKey(0)
        kv = [jax.random.randint(jax.random.fold_in(key, i),
                                 (b, hkv, l_buf, dh), -127, 127, jnp.int8)
              for i in (0, 1)]
        sc = [(jax.random.uniform(jax.random.fold_in(key, i),
                                  (b, hkv, 1, l_buf)) * 0.01
               ).astype(jnp.bfloat16) for i in (2, 3)]
        operands = (kv[0], sc[0], kv[1], sc[1])
        q = jax.random.normal(jax.random.fold_in(key, 9), (b, h, dh),
                              jnp.bfloat16)
        for case in cases:
            start, stop, live = windows(
                case, b, l_buf, np.random.default_rng(26)
            )
            empty = np.where(live, start, l_buf).astype(np.int32)
            live_tokens = int(((stop - start) * live).sum())
            roof = live_tokens * hkv * (2 * dh + 4) / 819e9 * 1e6
            variants = {}
            if parent is not None:
                variants["parent_stale"] = looped(parent, start, stop)
                variants["parent_empty"] = looped(parent, empty, stop)
            for g in granules:
                variants[f"g{g}"] = looped(
                    decode_attention, empty, stop, block_kv=g
                )
            variants["default"] = looped(decode_attention, empty, stop)
            for vname, fn in variants.items():
                us = us_a_call(fn, q, operands)
                results.append({
                    "geometry": name, "case": case, "variant": vname,
                    "us_a_call": us, "live_rows": int(live.sum()),
                    "live_tokens": live_tokens, "roofline_us": roof,
                })
                print(f"{name:5s} {case:11s} {vname:12s} {us:8.2f} us "
                      f"({roof / us * 100:5.1f}% of the live-KV roofline, "
                      f"{live_tokens} live tokens)", flush=True)
    with open(args.out, "w") as f:
        json.dump({"device": dev.device_kind, "results": results}, f, indent=1)


if __name__ == "__main__":
    main()
