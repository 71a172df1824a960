"""Attention dispatch: one call site, multiple backends.

Models call ``dot_product_attention``; this module picks the fastest
available implementation:

- on TPU, the Pallas flash-attention kernel (ops/pallas/flash_attention.py)
  — blocked online-softmax, O(S) memory, MXU-tiled;
- elsewhere (CPU tests, interpret mode), a reference XLA einsum path that
  XLA fuses well enough for correctness work.

Which shapes the kernel takes is decided HERE, before it is called
(``_flash_covers``): dense masks, S < 128 and offset-causal chunks
(Sq != Sk) are the reference path's by design.  Everything else on a
TPU backend must build: a kernel that raises there raises to the
caller — the O(S^2) reference never stands in for it silently.

The reference framework has no custom attention (torch SDPA inside
Catalyst models); this dispatch is where the TPU build spends its kernel
budget instead.
"""

from __future__ import annotations

import os
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from mlcomp_tpu.ops.pallas import on_tpu


def _flash_covers(q: jax.Array, k: jax.Array, causal: bool) -> bool:
    """The shapes the flash kernel is built for: both lengths at least
    a lane row (below that pad waste and launch overhead beat any
    kernel win), and a position-aligned diagonal when causal
    (offset-causal chunked prefill is the masked path's)."""
    s_q, s_k = q.shape[1], k.shape[1]
    return s_q >= 128 and s_k >= 128 and not (causal and s_q != s_k)


def _sharded_flash_attention(q, k, v, mesh, *, causal, scale,
                             kv_start, kv_stop):
    """``flash_attention`` under a device mesh: a shard_map island with
    batch over the data axes and heads over ``tp``.

    The chip's compiler refuses a bare Mosaic call with SPMD-sharded
    operands ("cannot be automatically partitioned"), so a train step
    over a dp/tp mesh never compiled on a TPU without this.  Attention
    is independent per (row, head) — and per GQA group, since ``tp``
    shards heads only when it divides BOTH head counts — so no
    cross-device math happens: the island only pins the layout the
    tp-sharded q/k/v projections already produce.  The same policy as
    ``sharded_decode_attention``."""
    from jax.sharding import PartitionSpec as P

    from mlcomp_tpu.ops.pallas.flash_attention import flash_attention

    from mlcomp_tpu.parallel.mesh import seq_shard_spec

    b = q.shape[0]
    # the (B, S, H, D) policy the ring/Ulysses wrappers share, with the
    # sequence axis left whole
    spec = seq_shard_spec(mesh, b, q.shape[2], k.shape[2], axis_name=None)
    rows_ax = spec[0]
    bounded = kv_start is not None or kv_stop is not None
    operands, in_specs = (q, k, v), (spec, spec, spec)
    if bounded:
        # the island takes both bounds or neither (the unbounded
        # kernel variants stay reachable)
        start = (
            jnp.zeros((b,), jnp.int32) if kv_start is None
            else kv_start.astype(jnp.int32)
        )
        stop = (
            jnp.full((b,), k.shape[1], jnp.int32) if kv_stop is None
            else jnp.broadcast_to(kv_stop, (b,)).astype(jnp.int32)
        )
        operands += (start, stop)
        in_specs += (P(rows_ax), P(rows_ax))

    def island(q, k, v, *bounds):
        lo, hi = bounds if bounds else (None, None)
        return flash_attention(
            q, k, v, causal=causal, scale=scale, kv_start=lo, kv_stop=hi,
        )

    return jax.shard_map(
        island, mesh=mesh, in_specs=in_specs, out_specs=spec,
        check_vma=False,
    )(*operands)


def reference_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    kv_start: Optional[jax.Array] = None,
    kv_stop: Optional[jax.Array] = None,
) -> jax.Array:
    """XLA path. q: (B, Sq, H, D); k,v: (B, Sk, Hkv, D) with Hkv | H (GQA —
    shared KV heads are broadcast, never materialized); mask broadcastable
    to (B, {1|Hkv}, Sq, Sk) (or (B, H, Sq, Sk) when Hkv == H);
    ``kv_start``/``kv_stop``: (B,) per-row valid-key windows (see
    flash_attention), folded into the mask here."""
    if kv_start is not None or kv_stop is not None:
        s_k, nb = k.shape[1], k.shape[0]
        cols = jnp.arange(s_k, dtype=jnp.int32)[None]
        lo = (
            jnp.zeros((nb, 1), jnp.int32) if kv_start is None
            else kv_start.astype(jnp.int32)[:, None]
        )
        hi = (
            jnp.full((nb, 1), s_k, jnp.int32) if kv_stop is None
            else kv_stop.astype(jnp.int32)[:, None]
        )
        window = ((cols >= lo) & (cols < hi))[:, None, None, :]  # (B,1,1,Sk)
        mask = window if mask is None else (mask.astype(jnp.bool_) & window)
    b, s_q, h, d = q.shape
    h_kv = k.shape[2]
    if h % h_kv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {h_kv}")
    rep = h // h_kv
    scale = scale if scale is not None else 1.0 / (d**0.5)
    qg = q.reshape(b, s_q, h_kv, rep, d)
    # fp32 softmax accumulation regardless of activation dtype
    logits = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k).astype(jnp.float32) * scale
    if causal:
        s_k = k.shape[1]
        cm = jnp.tril(jnp.ones((s_q, s_k), jnp.bool_), k=s_k - s_q)
        logits = jnp.where(cm[None, None, None], logits, -1e30)
    if mask is not None:
        m = mask.astype(jnp.bool_)
        if m.ndim == 4:
            if m.shape[1] == h and rep > 1:
                # per-q-head mask: materialize broadcast dims, then split
                # the head axis into (kv_head, rep) groups
                m = jnp.broadcast_to(m, (b, h, *m.shape[2:]))
                m = m.reshape(b, h_kv, rep, *m.shape[2:])
            else:
                m = m[:, :, None]  # (B, {1|Hkv}, 1, Sq, Sk)
        logits = jnp.where(m, logits, -1e30)
    weights = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", weights, v)
    return out.reshape(b, s_q, h, d)


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    kv_start: Optional[jax.Array] = None,
    kv_stop: Optional[jax.Array] = None,
) -> jax.Array:
    """Multi-head attention over (B, S, H, D) tensors.

    ``mask``: True = attend, broadcastable to (B, H, Sq, Sk).
    ``causal``: apply a causal triangle (decoder LM).
    ``kv_start``/``kv_stop``: (B,) per-row valid-key windows — the
    kernel-friendly form of key-padding masks (right padding: stop =
    lengths; left padding: start = pad counts).  Unlike a dense mask,
    these keep the flash-kernel path.
    """
    raw = os.environ.get("MLCOMP_TPU_FLASH", "auto").strip().lower()
    forced = raw in ("1", "true", "on", "yes")
    disabled = raw in ("0", "false", "off", "no")
    if not disabled and (forced or on_tpu()):
        if mask is not None:
            # the kernel covers causal/full/kv-window; arbitrary dense
            # masks stay on the XLA path (key padding: use kv_start/stop)
            if forced:
                warnings.warn(
                    "MLCOMP_TPU_FLASH forced on but a dense mask was passed; "
                    "using reference path",
                    stacklevel=2,
                )
        elif _flash_covers(q, k, causal):
            from mlcomp_tpu.ops.pallas.flash_attention import flash_attention
            from mlcomp_tpu.ops.quant import pallas_mesh

            mesh = pallas_mesh()
            if mesh is not None:
                return _sharded_flash_attention(
                    q, k, v, mesh, causal=causal, scale=scale,
                    kv_start=kv_start, kv_stop=kv_stop,
                )
            return flash_attention(
                q, k, v, causal=causal, scale=scale,
                kv_start=kv_start, kv_stop=kv_stop,
            )
    return reference_attention(
        q, k, v, mask=mask, causal=causal, scale=scale,
        kv_start=kv_start, kv_stop=kv_stop,
    )
