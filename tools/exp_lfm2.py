"""``synthesis-offline``'s model alone: LFM2-24B-A2B's five layers as the
configuration file gives them.

    # the sandbox, no chip: compile the model's part of the engine's two
    # programs for a DESCRIBED v5e (nothing runs) and print
    # memory_analysis() or the compiler's refusal
    JAX_PLATFORMS=cpu PYTHONPATH=/root/repo python tools/exp_lfm2.py --aot \
        [--slots 224,208]
    # the chip: the conv layer's two forms and the decode attention at
    # the cell's geometry, timed apart
    PYTHONPATH=/root/repo python tools/exp_lfm2.py
    PYTHONPATH=/root/repo python tools/exp_lfm2.py --tiny   # CPU rehearsal

``--aot`` lowers (a) K single-token steps of every slot under per-row
cursors, the cache donated and carried through a scan as the engine's
dispatch core carries it, greedy sampling, and (b) one 2,048-token
chunk of one row against its cache with ``last_logits_only``: what the
model contributes to ``jit_dispatch`` and to the chunk half of
``jit_fused``.  The code under test asks ``jax.default_backend()`` which
kernels to use and would see the CPU here, so this script, and nothing
in the program, tells it that it is compiling for a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _cell_config(tiny: bool):
    from benchmark import cells

    path = cells.HERE / "configs"
    if tiny:
        path = path / "_rehearsal"
    with open(path / "lfm2-24b-a2b-serve.json") as f:
        return json.load(f)


def _abstract(cfg, slots: int, chip):
    """(model, params, cache of ``slots`` rows, buffer length) as shapes
    on ``chip``."""
    import jax
    import jax.numpy as jnp

    from benchmark import cells
    from benchmark import weights as W
    from mlcomp_tpu.models import create_model
    from mlcomp_tpu.models.generation import decode_shapes

    arch = cells.architecture(cfg)
    d = arch.dims_of(cfg)
    svc = cfg["service"]
    l_buf = svc["prompt_buckets"][-1] + svc["max_new_buckets"][-1] + 1
    model = create_model(dict(cfg["model"]))
    on = lambda t: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), t
    )
    params = jax.eval_shape(
        lambda: W.program_params(arch, 7, d, jnp.bfloat16))
    cache = decode_shapes(model, slots, l_buf)["cache"]
    return model, on(params), on(cache), l_buf


def _report(tag, lowered):
    t0 = time.perf_counter()
    try:
        compiled = lowered.compile()
    except Exception as e:  # what the chip's compiler would refuse
        print(json.dumps({"program": tag, "refused": str(e)[:1500]}),
              flush=True)
        return
    m = compiled.memory_analysis()
    gb = 1e9
    text = compiled.as_text()
    print(json.dumps({
        "program": tag, "compile_s": round(time.perf_counter() - t0, 1),
        "arguments_gb": round(m.argument_size_in_bytes / gb, 3),
        "aliased_gb": round(m.alias_size_in_bytes / gb, 3),
        "temporaries_gb": round(m.temp_size_in_bytes / gb, 3),
        "custom_calls": {
            name: text.count(f"%{name}") for name in (
                "decode_attention", "grouped_matmul", "flash_fwd_kernel")
        },
    }), flush=True)


def aot(slots_list, cfg=None):
    """The two programs at each slot count, of ``cfg`` (default: this
    cell's); returns the described chip's sharding for what a caller
    compiles beside them (``tools/exp_longcat.py``)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    import mlcomp_tpu.ops.pallas as pallas

    # before the kernels' modules bind the two names
    pallas.on_tpu = lambda: True
    pallas.interpret_default = lambda: False

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    chip = SingleDeviceSharding(topo.devices[0])
    cfg = cfg or _cell_config(False)
    k = int(cfg["service"]["steps_per_dispatch"])
    chunk = int(cfg["service"]["prefill_chunk"])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    for slots in slots_list:
        model, params, cache, l_buf = _abstract(cfg, slots, chip)
        print(json.dumps({"slots": slots, "l_buf": l_buf, "cache": {
            jax.tree_util.keystr(p): [list(x.shape), str(x.dtype)]
            for p, x in jax.tree_util.tree_leaves_with_path(cache)
            if x.ndim}}), flush=True)

        def steps(params, cache, tok, start, cursor):
            slot = jnp.arange(l_buf)[None]

            def step(carry, _):
                cache, tok, cursor = carry
                kv_mask = (slot >= start[:, None]) & (slot <= cursor[:, None])
                logits, upd = model.apply(
                    {"params": params, "cache": cache}, tok[:, None],
                    decode=True, positions=(cursor - start)[:, None],
                    kv_mask=kv_mask, cache_cursor=cursor,
                    mutable=["cache", "counters"])
                new = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
                return (upd["cache"], new, cursor + 1), new

            (cache, _, _), toks = jax.lax.scan(
                step, (cache, tok, cursor), None, length=k)
            return cache, toks

        rows = spec((slots,), jnp.int32)
        _report(f"steps slots={slots} K={k}",
                jax.jit(steps, donate_argnums=(1,)).lower(
                    params, cache, rows, rows, rows))

    model, params, cache, l_buf = _abstract(cfg, 1, chip)

    def one_chunk(params, cache, ids, positions, kv_mask):
        logits, upd = model.apply(
            {"params": params, "cache": cache}, ids, decode=True,
            positions=positions, kv_mask=kv_mask, last_logits_only=True,
            mutable=["cache", "counters"])
        return upd["cache"], logits

    ids = spec((1, chunk), jnp.int32)
    _report(f"chunk tokens={chunk}",
            jax.jit(one_chunk, donate_argnums=(1,)).lower(
                params, cache, ids, ids, spec((1, l_buf), jnp.bool_)))
    return chip


def timed(tiny: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from exp_kimi import ms_of          # the script's own directory

    from mlcomp_tpu.models.short_conv import GatedShortConv
    from mlcomp_tpu.ops.pallas.decode_attention import (
        decode_attention,
        pick_buffer_len,
    )

    cfg = _cell_config(tiny)
    m, svc = cfg["model"], cfg["service"]
    rows, hidden = svc["batch_sizes"][-1], m["hidden"]
    chunk = svc["prefill_chunk"]
    heads, hkv, dh = m["heads_per_layer"][0], m["kv_heads"], m["head_dim"]
    l_buf = svc["prompt_buckets"][-1] + svc["max_new_buckets"][-1] + 1
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "rows": rows,
                      "hidden": hidden, "l_buf": l_buf}), flush=True)
    n = 2 if tiny else 20

    # the conv layer: one token a row under cursors, and one chunk
    layer = GatedShortConv(hidden, jnp.bfloat16, taps=m["conv_taps"])
    x = jax.random.normal(jax.random.PRNGKey(0), (rows, 1, hidden),
                          jnp.bfloat16)
    variables = layer.init(jax.random.PRNGKey(1), x, None, decode=True)
    params = jax.tree.map(
        lambda p: p.astype(jnp.bfloat16) if p.ndim == 2 and p.shape[0] > 8
        else p, variables["params"])
    live = jnp.ones((rows, l_buf), bool)

    def step(params, x, cache):
        out, upd = layer.apply(
            {"params": params, "cache": cache}, x, None, decode=True,
            kv_mask=live, cache_cursor=jnp.zeros((rows,), jnp.int32),
            mutable=["cache", "counters"])
        return out, upd["cache"]

    step = jax.jit(step, donate_argnums=(2,))
    ms, _ = ms_of(step, params, x, carry=variables["cache"], n=n)
    weights = sum(p.size * p.dtype.itemsize
                  for p in jax.tree.leaves(params))
    print(json.dumps({"conv.step_ms": ms, "rows": rows,
                      "weights_gb_s": weights / ms / 1e6}), flush=True)
    xc = jax.random.normal(jax.random.PRNGKey(2), (1, chunk, hidden),
                           jnp.bfloat16)
    one = layer.init(jax.random.PRNGKey(1), xc[:, :1], None, decode=True)

    @jax.jit
    def chunk_fn(params, x, cache):
        out, upd = layer.apply(
            {"params": params, "cache": cache}, x, None, decode=True,
            mutable=["cache", "counters"])
        return out, upd["cache"]

    ms, _ = ms_of(chunk_fn, params, xc, one["cache"], n=n)
    print(json.dumps({"conv.chunk_ms": ms, "tokens": chunk}), flush=True)

    # the decode attention at a head of ``dh`` in 128 lanes, three mean
    # contexts; GB/s of the PUBLISHED bytes (8 x (2 x 64 + 4) a token)
    dhp = -(-dh // 128) * 128
    lpad = pick_buffer_len(l_buf, hkv, dhp)
    k8 = jnp.ones((rows, hkv, lpad, dhp), jnp.int8)
    ks = jnp.ones((rows, hkv, 1, lpad), jnp.bfloat16)
    q = jax.random.normal(jax.random.PRNGKey(3), (rows, heads, dhp),
                          jnp.bfloat16)
    attend = jax.jit(lambda q, k8, ks, v8, vs, lo, hi: decode_attention(
        q, k8, ks, v8, vs, kv_start=lo, kv_stop=hi))
    rng = np.random.default_rng(0)
    for mean in ((24,) if tiny else (800, 1800, 3000)):
        hi = np.clip(rng.normal(mean, mean / 4, rows), 8, l_buf - 1)
        hi = jnp.asarray(hi.astype(np.int32))
        lo = jnp.zeros((rows,), jnp.int32)
        ms, _ = ms_of(attend, q, k8, ks, k8, ks, lo, hi, n=n)
        live_tokens = float(jnp.sum(hi))
        published = live_tokens * hkv * (2 * dh + 4)
        print(json.dumps({
            "decode_attention_ms": ms, "mean_context": mean,
            "published_gb_s": published / ms / 1e6,
            "stored_gb_s": live_tokens * hkv * (2 * dhp + 4) / ms / 1e6,
        }), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--slots", default="224")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    if args.aot:
        aot([int(s) for s in args.slots.split(",")])
    else:
        timed(args.tiny)


if __name__ == "__main__":
    main()
