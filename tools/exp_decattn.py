"""decode_attention alone on the chip: the walk's cost over window sets
shaped like the serve cells' traffic, this tree's kernel beside a
parent checkout's, and the granule sweep behind ``KV_BLOCK_BUDGET``.
Every call appends the row's token as the engine's step does (the
caches ride the loop's carry, donated).  Marginal timing: one jitted
``fori_loop`` a variant with a traced trip count, run at two counts; us
a call is the slope.

    python tools/exp_decattn.py [--parent DIR] [--out FILE] [--append]

Geometries and window sets: ``cell`` (InternLM2-1.8B's serve cells: B
48, H 16, Hkv 8, L 2560; ``steady`` / ``steady_long`` / ``offline`` /
``full``), ``smoke`` (the 1.2B smoke's whole buffer: B 8, Hkv 16, L
2304), ``rollout`` (Laguna's cell: B 48, H 72, Hkv 8, L 1152, prompts
~128 in the 256 bucket and 512-768 tokens decoded; a window of 512 and
none) and ``mixedlen`` (SmallThinker's: B 32, H 28, Hkv 4, L 13,056,
contexts of 4-12k; a window of 4,096 and none).  Variants a set:
``parent`` (``--parent`` names a checkout whose ``decode_attention`` is
timed on the same windows, its output and the bytes it leaves in the
caches compared with this tree's on the device first), ``change`` (this
tree), ``copy_only`` (this tree's kernel with ONE line of its source
rewritten: a trip copies the trimmed width and attends the whole
granule, which says whether a token's cost is the copy or the
arithmetic; its output is not read; ``--dissect`` adds ``no_attend``
and ``no_copy``, the kernel without its flash update and without its
fetches) and, for ``cell`` and ``smoke``, ``g<N>``: this tree at a
fixed granule.  ``--geometry cell,smoke`` runs a part.

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
import json
import statistics
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from mlcomp_tpu.ops.pallas import decode_attention as this_tree
from mlcomp_tpu.ops.pallas.decode_attention import decode_attention

N_LO, N_HI, REPEATS = 64, 448, 5
BUCKETS = (256, 512, 1024, 2048)
WINDOWS = {"rollout_window": 512, "mixedlen_window": 4096}


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
    elif case.startswith("rollout"):
        # Laguna's cell: every slot full, a short task, a long answer
        live = np.ones(b, bool)
        win = [
            row(int(np.clip(rng.lognormal(np.log(128), 0.5), 32, 256)),
                int(rng.integers(512, 768)))
            for _ in range(b)
        ]
    elif case.startswith("mixedlen"):
        # SmallThinker's: one bucket of 12,288, prompts of 512-12,288
        live = np.ones(b, bool)
        win = [
            (12288 - int(np.clip(rng.lognormal(np.log(4096), 0.8),
                                 512, 12288)),
             12288 + int(rng.integers(0, 512)))
            for _ in range(b)
        ]
    else:
        raise ValueError(case)
    start, stop = (np.array(x, np.int32) for x in zip(*win))
    if case.endswith("_window"):   # a window layer reads its last tokens
        start = np.maximum(start, stop - WINDOWS[case])
    return start, stop, live


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


def layer_step(variant, q, caches, new, start, cur):
    """One layer's write of a token a row and its attention."""
    from mlcomp_tpu.models.transformer import _row_cursor_dus

    k8, ks, v8, vs = caches
    kq, ks_new, vq, vs_new = new
    kw = dict(kv_start=start, kv_stop=cur + 1)
    if variant == "append":
        variant = decode_attention
    if callable(variant):     # a decode_attention: this tree's or another
        out, *caches = variant(q, *caches, append=new, **kw)
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


def random_caches(key, b, hkv, l_buf, dh):
    """(k8, ks, v8, vs): int8 values and bfloat16 scales, from ``key``."""
    kv = [jax.random.randint(jax.random.fold_in(key, i),
                             (b, hkv, l_buf, dh), -127, 127, jnp.int8)
          for i in (0, 1)]
    sc = [(jax.random.uniform(jax.random.fold_in(key, i),
                              (b, hkv, 1, l_buf)) * 0.01
           ).astype(jnp.bfloat16) for i in (2, 3)]
    return kv[0], sc[0], kv[1], sc[1]


def random_step(key, b, h, hkv, dh):
    """(q, the new token's (kq, ks_new, vq, vs_new)), from ``key``."""
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
    return q, new


def append_cases(args, dev):
    """The table behind PR 29's choice of form (module docstring)."""
    b, h, hkv, l_buf, dh = 48, 16, 8, 2560, 128
    if args.tiny:
        b, h, hkv = 12, 4, 2
    key = jax.random.PRNGKey(29)
    caches = random_caches(key, b, hkv, l_buf, dh)
    q, new = random_step(key, b, h, hkv, dh)
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


def load_kernel(path, *rewrites):
    """The ``decode_attention`` module at ``path`` as a module of its
    own, with each of ``rewrites`` = (old, new) applied to its source
    first (the old text must occur exactly once)."""
    with open(path) as f:
        src = f.read()
    for old, new in rewrites:
        if src.count(old) != 1:
            raise SystemExit(f"{path}: expected one {old!r}")
        src = src.replace(old, new)
    mod = types.ModuleType("decode_attention_at_" + str(abs(hash(path))))
    mod.__file__ = path
    exec(compile(src, path, "exec"), mod.__dict__)
    return mod


# this tree's kernel with a line or two of its source rewritten: what a
# trip costs without one of its parts (outputs are not read)
REWRITES = {
    # a trip copies the trimmed width and attends the whole granule
    "copy_only": [
        ("attend_cols = pl.ds(0, w)", "attend_cols = pl.ds(0, granule)"),
    ],
    # the copies, the patch and the control, no flash update
    "no_attend": [
        ("""                    _flash_block_update(
                        q, k_buf[slot, :, attend_cols, :]""",
         """                    (lambda *a: None)(
                        q, k_buf[slot, :, attend_cols, :]"""),
    ],
    # the flash update on whatever the slot holds: no fetch
    "no_copy": [
        ("""            for cp in copies(r, col, w, slot):
                cp.start()""", "            pass"),
        ("""                    for cp in copies(r, col, w, slot):
                        cp.wait()""", "                    pass"),
    ],
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent")
    ap.add_argument("--out", default="chiprun_out/decattn_sweep.json")
    ap.add_argument("--tiny", action="store_true",
                    help="a CPU rehearsal of the control flow: no timing")
    ap.add_argument("--append", action="store_true",
                    help="the KV-append cases instead of the sweep")
    ap.add_argument("--geometry", default="",
                    help="comma-separated geometries to run (default all)")
    ap.add_argument("--dissect", action="store_true",
                    help="also time the kernel without its flash update "
                         "and without its fetches")
    args = ap.parse_args()
    if args.tiny:
        global N_LO, N_HI, REPEATS
        N_LO, N_HI, REPEATS = 1, 2, 1

    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    if args.append:
        if args.out == ap.get_default("out"):
            args.out = "chiprun_out/decattn_append.json"
        return append_cases(args, dev)
    kernels = {}
    if args.parent:
        kernels["parent"] = load_kernel(
            f"{args.parent}/mlcomp_tpu/ops/pallas/decode_attention.py"
        ).decode_attention
    kernels["change"] = decode_attention
    for name in ("copy_only", "no_attend", "no_copy")[:3 if args.dissect
                                                      else 1]:
        kernels[name] = load_kernel(
            this_tree.__file__, *REWRITES[name]
        ).decode_attention
    results = []
    geometries = [
        ("cell", 48, 16, 8, 2560, ("steady", "steady_long", "offline", "full"),
         (128, 256, 512)),
        ("smoke", 8, 16, 16, 2304, ("full",), (128, 256, 768)),
        ("rollout", 48, 72, 8, 1152, ("rollout_window", "rollout"), ()),
        ("mixedlen", 32, 28, 4, 13056, ("mixedlen_window", "mixedlen"), ()),
    ]
    if args.tiny:
        geometries = [
            ("tiny", 12, 4, 2, 2560, ("steady", "offline"), (128,)),
            ("tinymix", 3, 7, 1, 13056, ("mixedlen_window",), ()),
        ]
    for name, b, h, hkv, l_buf, cases, granules in geometries:
        if args.geometry and name not in args.geometry.split(","):
            continue
        dh = 128
        granule = this_tree.auto_block_kv(l_buf, hkv, dh)
        key = jax.random.PRNGKey(0)
        q, new = random_step(key, b, h, hkv, dh)
        for case in cases:
            start, stop, live = windows(
                case, b, l_buf, np.random.default_rng(26)
            )
            # a row without a request is handed an empty window
            start = np.where(live, start, l_buf).astype(np.int32)
            live_tokens = int(((stop - start) * live).sum())
            roof = live_tokens * hkv * (2 * dh + 4) / 819e9 * 1e6
            moved = {"change": int(this_tree.kv_tokens_fetched(
                start, stop, l_buf, granule).sum())}
            for v in REWRITES:
                moved[v] = moved["change"]
            # whole granules: what the walk moved before it trimmed
            moved["parent"] = int(np.where(
                stop > start,
                (-(-stop // granule) - start // granule) * granule, 0
            ).sum())
            # the rewritten kernels last: one that fetches nothing
            # writes what its scratch held back into the caches
            variants = {v: k for v, k in kernels.items()
                        if v not in REWRITES}
            for g in granules:
                variants[f"g{g}"] = functools.partial(
                    decode_attention, block_kv=g
                )
                moved[f"g{g}"] = int(this_tree.kv_tokens_fetched(
                    start, stop, l_buf, g).sum())
            variants.update(
                (v, k) for v, k in kernels.items() if v in REWRITES
            )
            start, cur = jnp.asarray(start), jnp.asarray(stop - 1)
            caches = random_caches(key, b, hkv, l_buf, dh)
            same = None
            if "parent" in kernels:
                one, two = (
                    jax.jit(functools.partial(layer_step, kernels[v]))(
                        q, caches, new, start, cur
                    ) for v in ("parent", "change")
                )
                gap = float(jnp.max(jnp.abs(
                    one[0].astype(jnp.float32) - two[0].astype(jnp.float32)
                )))
                same = all(
                    bool(jnp.array_equal(x, y))
                    for x, y in zip(one[1], two[1])
                ) and bool(jnp.isfinite(two[0].astype(jnp.float32)).all())
                del one, two
                print(f"{name} {case}: change leaves the parent's bytes "
                      f"in all four caches and finite outputs: {same}; "
                      f"largest output gap {gap:.3g}", flush=True)
            for vname, fn in variants.items():
                us, caches = us_a_layer(
                    looped_layer(fn, new, start, cur), q, caches
                )
                results.append({
                    "geometry": name, "case": case, "variant": vname,
                    "us_a_call": us, "live_rows": int(live.sum()),
                    "live_tokens": live_tokens, "roofline_us": roof,
                    "tokens_moved": moved[vname],
                    "bytes_equal_parent": same,
                })
                print(f"{name:8s} {case:15s} {vname:9s} {us:8.2f} us "
                      f"({roof / us * 100:5.1f}% of the live-KV roofline; "
                      f"{live_tokens} live tokens, {moved[vname]} moved)",
                      flush=True)
    with open(args.out, "w") as f:
        json.dump({"device": dev.device_kind, "results": results}, f, indent=1)


if __name__ == "__main__":
    main()
