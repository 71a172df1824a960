"""``document-qa-offline``'s model alone: LongCat-Flash-Chat's four
shortcut layers as the configuration file gives them, compiled for a
DESCRIBED v5e (no chip, nothing runs):

    JAX_PLATFORMS=cpu PYTHONPATH=/root/repo python tools/exp_longcat.py \
        [--slots 24,16] [--reference 0|1]

prints ``memory_analysis()`` or the compiler's refusal of (a) K
single-token steps of every slot under per-row cursors, the cache
donated and carried through a scan as the engine's dispatch core
carries it, (b) one 2,048-token chunk of one row against its cache with
``last_logits_only`` (what the model contributes to ``jit_dispatch``
and to the chunk half of ``jit_fused``: ``tools/exp_lfm2.py``'s two
programs, of this configuration), and with ``--reference 1`` (c) the
float32 reference's layer program as ``check_serve`` runs it at the cell's 6
sampled rows of 8,448 positions, alone and with the int8 control beside
it: 5 GB of float32 weights a layer and pass have to fit the chip
after the service is gone.  ~40 s a program.
"""

from __future__ import annotations

import argparse
import json
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _config():
    from benchmark import cells

    with open(cells.HERE / "configs" / "longcat-flash-chat-serve.json") as f:
        return json.load(f)


def reference_layer(cfg, chip, rows: int, control: bool) -> None:
    """``check_serve.serve_readings``' program for every layer of a
    kind, at ``rows`` sampled requests."""
    import jax
    import jax.numpy as jnp
    from exp_lfm2 import _report        # the script's own directory

    from benchmark import cells
    from benchmark import weights as W
    from benchmark.reference.check_serve import passes_of
    from benchmark.reference.quant import quantize_leaves

    M = cells.architecture(cfg)
    d = M.dims_of(cfg)
    svc = cfg["service"]
    pad_len = svc["prompt_buckets"][-1] + svc["max_new_buckets"][-1]
    blk = M.rows_per_block(d, pad_len)
    n_pad = -(-rows // blk) * blk
    passes = passes_of(cfg, control)
    pos = jnp.broadcast_to(jnp.arange(pad_len, dtype=jnp.int32),
                           (blk, pad_len))

    def layer_all(key, i, xs):
        w = M.layer_weights(key, i, d, jnp.bfloat16, "shortcut")
        out = []
        for (_, q, _), x in zip(passes, xs):
            wq = quantize_leaves(w, q, M.CONTRACT_AXES)
            xb = x.reshape(n_pad // blk, blk, pad_len, d["hidden"])
            y = jax.lax.map(
                lambda b: M.layer(b, wq, pos, d, "shortcut"), xb)
            out.append(y.reshape(x.shape))
        return tuple(out)

    spec = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=chip)  # noqa: E731
    key = jax.eval_shape(lambda: W.seed_key(7))
    xs = tuple(spec((n_pad, pad_len, d["hidden"]), jnp.float32)
               for _ in passes)
    _report(f"reference layer rows={rows} blk={blk} passes={len(passes)}",
            jax.jit(layer_all).lower(
                spec(key.shape, key.dtype), spec((), jnp.int32), xs))


def aot(slots_list, reference: bool) -> None:
    from exp_lfm2 import aot as model_programs   # the script's own directory

    cfg = _config()
    chip = model_programs(slots_list, cfg)
    if reference:
        for control in (False, True):
            reference_layer(cfg, chip, 6, control)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", default="24")
    ap.add_argument("--reference", type=int, default=0)
    args = ap.parse_args()
    aot([int(s) for s in args.slots.split(",")], bool(args.reference))


if __name__ == "__main__":
    main()
