"""The grouped matmul's tile sweep on the chip (PYTHONPATH=/root/repo).

The routed part of the expert layer as ``models/moe.py``
``RoutedExperts`` runs it (counting sort, row gather, the gated front
half and the down projection through the kernel, the un-sort and the
weighted sum), at one model's widths (``--hidden``, ``--width``,
``--experts`` published of which ``--held`` are here, ``--top-k``,
``--gate``), for each ``--tokens`` count (a prefill chunk's, a decode
step's), row tile (``--tm``) and weight block (``--blocks``: ``auto``
is ``auto_block_n``'s, or pairs ``up x down`` as ``256x1024,128x512``),
under two draws of the routing: ``uniform`` over the published experts,
and ``skewed``, a Zipf-like split (an expert's chance falls as its rank
to the power ``--skew``, ranks dealt at random over the experts, so a
few take several times the mean).  Per setting one JSON line:
microseconds of the whole layer call and of the two kernel calls alone,
the least time the chip could take for those two calls by
``benchmark/rooflines/grouped_matmul.py``'s own count (rows held,
experts touched) at the chip's published peaks, that over the kernels'
time, and the tiles' fill (rows held over tiles used x rows a tile).
``--megablox 1`` adds ``jax.experimental.pallas.ops.tpu.megablox.gmm``
on the same sorted rows (SwiGLU only).  The sweeps the kernel's
constants cite: PR 28 ``--tokens 48,256`` (the defaults' widths,
``--tm 16,32,64 --blocks 256x1024,128x512,512x1536``), PR 42 the two
2,048-token chunk shapes and their steps at the defaults (the comment
on ``ROW_TILES`` in ``ops/pallas/grouped_matmul.py``).
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import cells
from benchmark.device import PEAKS
from mlcomp_tpu.ops.pallas.grouped_matmul import (
    GATES,
    group_layout,
    grouped_matmul,
    padded_rows,
)


def kernels_fn(block_up, block_down, gate):
    def fn(rows, tile_group, tiles_used, w_gate, w_up, w_down):
        act = grouped_matmul(rows, w_gate, tile_group, tiles_used, w2=w_up,
                             block_n=block_up, gate=gate)
        return grouped_matmul(act, w_down, tile_group, tiles_used,
                              block_n=block_down)

    return jax.jit(fn)


def layout_of(group, held, tm, k):
    return group_layout(
        group, held, tm,
        source=jnp.arange(group.shape[0], dtype=jnp.int32) // k,
    )


def layer_fn(tm, block_up, block_down, held, k, gate):
    """``RoutedExperts``' routed part from the chosen experts on."""
    kernels = kernels_fn(block_up, block_down, gate)

    def fn(x, group, gates, w_gate, w_up, w_down):
        lay = layout_of(group, held, tm, k)
        rows = jnp.take(x, lay.row_source, axis=0)
        out = kernels(rows, lay.tile_group, lay.tiles_used,
                      w_gate, w_up, w_down)
        is_held = lay.dest < lay.row_source.shape[0]
        picked = jnp.where(
            is_held[:, None],
            jnp.take(out, jnp.where(is_held, lay.dest, 0), axis=0), 0,
        ).reshape(x.shape[0], k, x.shape[1])
        y = jnp.einsum("tkd,tk->td", picked.astype(jnp.float32), gates)
        return y.astype(x.dtype), lay.sizes, lay.tiles_used

    return jax.jit(fn)


def megablox_fn(held, k):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    def fn(x, group, gates, w_gate, w_up, w_down):
        keep = group < held
        order = jnp.argsort(jnp.where(keep, group, held), stable=True)
        rows = jnp.take(x, order // k, axis=0)
        sizes = jnp.bincount(jnp.where(keep, group, held),
                             length=held + 1)[:held].astype(jnp.int32)
        mm = lambda l, r: gmm(l, r, sizes, tiling=(128, 1024, 512))  # noqa
        act = jax.nn.silu(mm(rows, w_gate)) * mm(rows, w_up)
        return mm(act.astype(x.dtype), w_down), sizes, jnp.zeros((1,), jnp.int32)

    return jax.jit(fn)


def draw(rng, tokens, k, experts, skew):
    """(tokens * k,) distinct experts a token: the ``k`` largest of
    log-chance plus Gumbel noise (a draw without replacement)."""
    ranks = rng.permutation(experts) + 1.0
    logp = -skew * np.log(ranks)
    noise = rng.gumbel(size=(tokens, experts))
    return np.argsort(-(logp[None] + noise), axis=1)[:, :k] \
        .reshape(-1).astype(np.int32)


def timed(fn, args, reps):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6, out


def roofline_us(rows, touched, held, h, f, peaks):
    """The least time for the layer's two kernel calls, by the
    benchmark's own count of their operations and bytes (it reads the
    widths from the op's text, the rows and experts from the counts)."""
    mod = cells.roofline("grouped_matmul")
    ctx = {"moe_rows_per_call": rows, "moe_experts_per_call": touched}
    least = 0.0
    for k, n, stacks in ((h, f, 2), (f, h, 1)):
        operands = ["s32[1] %tg", "s32[1] %used", f"bf16[{rows},{k}] %x"] + [
            f"bf16[{held},{k},{n}] %w{i}" for i in range(stacks)]
        op = (f"%grouped_matmul = bf16[{rows},{n}] custom-call("
              f"{', '.join(operands)}), custom_call_target=\"tpu_custom_call\"")
        flops, nbytes = mod.cost(op, ctx)
        least += max(flops / peaks["bf16_flops"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return least * 1e6


def ints(text):
    return [int(v) for v in text.split(",") if v]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--hidden", type=int, default=3072)
    ap.add_argument("--width", type=int, default=1024)
    ap.add_argument("--experts", type=int, default=256)
    ap.add_argument("--held", type=int, default=128)
    ap.add_argument("--top-k", type=int, default=10)
    ap.add_argument("--gate", default="silu", choices=sorted(GATES))
    ap.add_argument("--tokens", type=ints, default=[48, 256])
    ap.add_argument("--tm", type=ints, default=[16, 32, 64, 128, 256])
    ap.add_argument("--blocks", default="auto")
    ap.add_argument("--routing", default="uniform,skewed")
    ap.add_argument("--skew", type=float, default=0.6)
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--megablox", type=int, default=0)
    ap.add_argument("--label", default="")
    args = ap.parse_args()
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform}),
          flush=True)
    # the peaks only where the device has published ones: a rehearsal
    # on the CPU prints no roofline time
    peaks = PEAKS.get(dev.device_kind)
    h, f, e, k = args.hidden, args.width, args.held, args.top_k
    blocks = [(None, None)] if args.blocks == "auto" else [
        tuple(int(v) for v in pair.split("x"))
        for pair in args.blocks.split(",")
    ]
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    w_gate = (jax.random.normal(ks[0], (e, h, f)) * h ** -0.5).astype(jnp.bfloat16)
    w_up = (jax.random.normal(ks[1], (e, h, f)) * h ** -0.5).astype(jnp.bfloat16)
    w_down = (jax.random.normal(ks[2], (e, f, h)) * f ** -0.5).astype(jnp.bfloat16)
    weights = (w_gate, w_up, w_down)
    for tokens in args.tokens:
        x = jax.random.normal(ks[3], (tokens, h)).astype(jnp.bfloat16)
        gates = jax.nn.softmax(jax.random.normal(ks[4], (tokens, k)), axis=-1)
        settings = [("own", tm, bu, bd)
                    for tm in args.tm for bu, bd in blocks]
        if args.megablox:
            settings.append(("megablox", 128, 512, 512))
        # one compile a setting, shared by the draws
        fns = {}
        for routing in args.routing.split(","):
            rng = np.random.default_rng(0)
            group = draw(rng, tokens, k, args.experts,
                         args.skew if routing == "skewed" else 0.0)
            group = jnp.asarray(np.where(group < e, group, e))
            for kind, tm, bu, bd in settings:
                line = {"label": args.label, "tokens": tokens, "top_k": k,
                        "routing": routing, "kind": kind, "tm": tm,
                        "block_up": bu, "block_down": bd}
                try:
                    if (kind, tm, bu, bd) not in fns:
                        fns[kind, tm, bu, bd] = (
                            layer_fn(tm, bu, bd, e, k, args.gate)
                            if kind == "own" else megablox_fn(e, k),
                            kernels_fn(bu, bd, args.gate),
                            jax.jit(lambda g, tm=tm: layout_of(g, e, tm, k)),
                        )
                    whole, kernels, layout = fns[kind, tm, bu, bd]
                    us, (_, sizes, used) = timed(
                        whole, (x, group, gates) + weights, args.reps)
                    line["us_per_layer_call"] = round(us, 1)
                    if kind == "own":
                        lay = layout(group)
                        rows = jnp.take(x, lay.row_source, axis=0)
                        us_k, _ = timed(
                            kernels,
                            (rows, lay.tile_group, lay.tiles_used) + weights,
                            args.reps)
                        line["us_kernels"] = round(us_k, 1)
                except Exception as err:  # a tile the compiler refuses
                    print(json.dumps({**line, "refused": str(err)[:300]}),
                          flush=True)
                    continue
                sizes = np.asarray(sizes)
                held_rows, touched = int(sizes.sum()), int(np.sum(sizes > 0))
                line.update(rows_held=held_rows, experts_touched=touched,
                            largest_over_mean=round(
                                float(sizes.max()) / max(sizes.mean(), 1e-9), 2))
                if kind == "own":
                    line["padded_rows"] = padded_rows(tokens * k, e, tm)
                    line["tile_fill"] = round(
                        held_rows / max(int(np.asarray(used)[0]) * tm, 1), 3)
                    if peaks:
                        least = roofline_us(held_rows, touched, e, h, f, peaks)
                        line["roofline_us"] = round(least, 1)
                        line["kernels_share_of_roofline"] = round(
                            least / line["us_kernels"], 3)
                print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
