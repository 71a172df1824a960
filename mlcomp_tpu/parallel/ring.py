"""Ring attention: sequence/context parallelism over the ``sp`` mesh axis.

Long-context attention where the sequence is sharded across devices: each
device keeps its Q shard resident and the K/V shards rotate around the ring
via ``lax.ppermute`` (XLA lowers this to ICI neighbor exchange), with the
softmax accumulated online — max/sum renormalization per incoming block —
so no device ever materializes more than its (S/n)² tile of logits.

The reference has nothing like this (its only parallelism is DDP
data-parallel); sequence parallelism is a first-class capability of the
TPU build. The math is the same blocked online softmax as the Pallas flash
kernel (ops/pallas/flash_attention.py), lifted one level up: blocks are
device shards, the inner loop is a ``lax.scan`` over ring steps, and the
rotation overlaps with the block compute under XLA's scheduler (the
ppermute for step i+1 has no data dependency on step i's block compute).

Opt-in (``use_flash=True`` / model ``seq_parallel: ring_flash``), the
per-block compute runs the Pallas flash kernel (``flash_attention_lse``)
and blocks merge by logsumexp — MXU-tiled inner attention with the lse
cotangent handled exactly in the kernel backward.  The pure-jnp
einsum-tile path is the reference implementation and the default.

Differentiable by construction (pure jnp + ppermute, which is its own
transpose), so the backward pass is another ring pass — no custom VJP.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


# KV chunk for the within-shard online softmax: logits materialize as
# (Sq, KV_CHUNK) tiles instead of the full (Sq, S_local) — at S_local=4k+
# the un-chunked tile would be GBs of fp32 per ring step (XLA does not
# fuse einsum→softmax→einsum into a streaming loop on its own)
KV_CHUNK = 1024


def _tile_attn(q, k, v, row0, col0, causal, scale):
    """One Q-shard × KV-chunk tile, GQA-aware, fp32 accumulation.

    Returns (unnormalized_out, tile_max, tile_sum) for online merging.
    q: (B, Sq, H, D); k/v: (B, Sk, Hkv, D); row0/col0: global offsets.
    """
    b, s_q, h, d = q.shape
    s_k, h_kv = k.shape[1], k.shape[2]
    rep = h // h_kv
    qg = q.reshape(b, s_q, h_kv, rep, d)
    s = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k).astype(jnp.float32) * scale
    if causal:
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 0)
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 1)
        s = jnp.where((rows >= cols)[None, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)                    # (B,Hkv,rep,Sq,1)
    # clamp fully-masked rows: exp(NEG_INF - NEG_INF) would be exp(0) = 1
    m_safe = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(s - m_safe) * (s > NEG_INF / 2).astype(jnp.float32)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhrqk,bkhd->bhrqd", p.astype(v.dtype), v).astype(jnp.float32)
    return o, m_safe, l


def _online_merge(acc, m, l, o_b, m_b, l_b):
    """Online-softmax merge of a new (out, max, sum) tile into the running
    accumulators — the ONE definition both the inner KV-chunk scan and the
    outer ring scan use."""
    m_new = jnp.maximum(m, m_b)
    alpha = jnp.exp(m - m_new)
    beta = jnp.exp(m_b - m_new)
    return acc * alpha + o_b * beta, m_new, l * alpha + l_b * beta


def _zero_carry(b, h_kv, rep, s_q, d, like):
    """(acc0, m0, l0) scan carries.  ``+ zero`` imprints ``like``'s
    device-varying axes: under shard_map the carry types must match the
    (varying) tile outputs or the scan carry check fails."""
    zero = like.reshape(-1)[0].astype(jnp.float32) * 0.0
    return (
        jnp.zeros((b, h_kv, rep, s_q, d), jnp.float32) + zero,
        jnp.full((b, h_kv, rep, s_q, 1), NEG_INF / 2, jnp.float32) + zero,
        jnp.zeros((b, h_kv, rep, s_q, 1), jnp.float32) + zero,
    )


def _block_attn(q, k, v, row0, col0, causal, scale):
    """Q-shard × KV-shard attention with (Sq, KV_CHUNK)-bounded logits.

    Same (unnormalized_out, max, sum) contract as :func:`_tile_attn`; when
    the KV shard exceeds ``KV_CHUNK`` it is streamed through an inner
    ``lax.scan`` (plus one remainder tile when the shard is not a chunk
    multiple — the memory bound must not silently vanish for ragged
    shards).  Pure jnp, so the backward pass stays automatic;
    ``jax.checkpoint`` on the tile keeps the scan from saving per-chunk
    logits for it.
    """
    b, s_q, h, d = q.shape
    s_k, h_kv = k.shape[1], k.shape[2]
    rep = h // h_kv
    if s_k <= KV_CHUNK:
        return _tile_attn(q, k, v, row0, col0, causal, scale)

    tile = jax.checkpoint(partial(_tile_attn, causal=causal, scale=scale))
    nc = s_k // KV_CHUNK
    main = nc * KV_CHUNK

    def chunk_step(carry, ci):
        acc, m, l = carry
        k_c = jax.lax.dynamic_slice_in_dim(k, ci * KV_CHUNK, KV_CHUNK, 1)
        v_c = jax.lax.dynamic_slice_in_dim(v, ci * KV_CHUNK, KV_CHUNK, 1)
        o_b, m_b, l_b = tile(q, k_c, v_c, row0, col0 + ci * KV_CHUNK)
        return _online_merge(acc, m, l, o_b, m_b, l_b), None

    (acc, m, l), _ = jax.lax.scan(
        chunk_step, _zero_carry(b, h_kv, rep, s_q, d, q), jnp.arange(nc)
    )
    if main < s_k:
        o_b, m_b, l_b = tile(q, k[:, main:], v[:, main:], row0, col0 + main)
        acc, m, l = _online_merge(acc, m, l, o_b, m_b, l_b)
    return acc, m, l


def _merge_normalized(out, lse, o_b, l_b):
    """Merge two NORMALIZED partial results via their logsumexps (the
    flash-block form of the online merge; sentinel lse = NEG_INF/2 means
    "no contribution" and stays finite so the exps never produce NaN)."""
    l_new = jnp.logaddexp(lse, l_b)
    a = jnp.exp(lse - l_new)[..., None]
    b_ = jnp.exp(l_b - l_new)[..., None]
    return out * a + o_b * b_, l_new


def _ring_flash(q, k, v, axis_name, causal, scale):
    """Ring pass whose per-shard block compute is the Pallas flash kernel
    (ops/pallas/flash_attention.py flash_attention_lse) instead of XLA
    einsum tiles: each Q-shard x KV-shard block runs MXU-tiled with O(S)
    memory, and blocks merge by logsumexp.  Causality is decided per
    RING STEP (before = full block, diagonal = causal kernel, after =
    skip), so the kernel never needs global offsets."""
    from mlcomp_tpu.ops.pallas.flash_attention import flash_attention_lse

    n = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    b, s_q, h, d = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    def full_block(k_blk, v_blk):
        o, l = flash_attention_lse(q, k_blk, v_blk, causal=False, scale=scale)
        return o.astype(jnp.float32), l

    def diag_block(k_blk, v_blk):
        o, l = flash_attention_lse(q, k_blk, v_blk, causal=True, scale=scale)
        return o.astype(jnp.float32), l

    def skip_block(k_blk, v_blk):
        return (
            jnp.zeros((b, s_q, h, d), jnp.float32),
            jnp.full((b, s_q, h), NEG_INF / 2, jnp.float32),
        )

    def step(carry, i):
        k_blk, v_blk, out, lse = carry
        src = (me - i) % n                      # whose shard we hold now
        # rotate first: the collective has no dependency on this step's
        # compute, so XLA can overlap ICI transfer with the kernel
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        if causal:
            o_b, l_b = jax.lax.cond(
                src == me,
                diag_block,
                lambda kb, vb: jax.lax.cond(
                    src < me, full_block, skip_block, kb, vb
                ),
                k_blk, v_blk,
            )
        else:
            o_b, l_b = full_block(k_blk, v_blk)
        out, lse = _merge_normalized(out, lse, o_b, l_b)
        return (k_nxt, v_nxt, out, lse), None

    zero = q.reshape(-1)[0].astype(jnp.float32) * 0.0  # imprint varying type
    out0 = jnp.zeros((b, s_q, h, d), jnp.float32) + zero
    lse0 = jnp.full((b, s_q, h), NEG_INF / 2, jnp.float32) + zero
    (_, _, out, _), _ = jax.lax.scan(
        step, (k, v, out0, lse0), jnp.arange(n), length=n
    )
    return out.astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "sp",
    causal: bool = False,
    scale: Optional[float] = None,
    use_flash: Optional[bool] = None,
) -> jax.Array:
    """Attention over a sequence sharded on ``axis_name``.

    Call INSIDE shard_map/jit-with-sharding: q, k, v are the per-device
    shards (B, S_local, H|Hkv, D), sequence-contiguous in ring order.
    Returns the local output shard (B, S_local, H, D).

    ``use_flash``: run each Q-shard × KV-shard block through the Pallas
    flash kernel.  None currently means False (opt-in — see the inline
    comment for the measurement caveat); the einsum-tile path is the
    reference implementation and the default.
    """
    n = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    scale = scale if scale is not None else 1.0 / (d**0.5)
    row0 = me * s_q
    h_kv = k.shape[2]
    rep = h // h_kv

    from mlcomp_tpu.ops.pallas.flash_attention import LANES

    tileable = (
        s_q >= LANES and s_k >= LANES and s_q % LANES == 0
        and s_k % LANES == 0 and s_q == s_k
    )
    if use_flash is None:
        # OPT-IN for now: the flash-block path is numerically verified
        # (fwd + bwd vs the einsum path, tests/test_ring_attention.py)
        # but not measured on this chip, so an auto-on default cannot
        # be justified yet.  Flip after profiling it on multi-chip
        # hardware.
        use_flash = False
    if use_flash:
        if not tileable:
            raise NotImplementedError(
                f"ring flash path needs equal lane-tileable shards; got "
                f"{s_q}/{s_k}"
            )
        return _ring_flash(q, k, v, axis_name, causal, scale)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, i):
        k_blk, v_blk, acc, m, l = carry
        src = (me - i) % n                      # whose shard we hold now
        # rotate first: the collective has no dependency on this step's
        # compute, so XLA can overlap ICI transfer with the einsums
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        o_b, m_b, l_b = _block_attn(q, k_blk, v_blk, row0, src * s_k, causal, scale)
        acc, m, l = _online_merge(acc, m, l, o_b, m_b, l_b)
        return (k_nxt, v_nxt, acc, m, l), None

    acc0, m0, l0 = _zero_carry(b, h_kv, rep, s_q, d, q)
    (_, _, acc, m, l), _ = jax.lax.scan(
        step, (k, v, acc0, m0, l0), jnp.arange(n), length=n
    )
    out = acc / jnp.maximum(l, 1e-30)
    # (B, Hkv, rep, Sq, D) -> (B, Sq, H, D)
    out = jnp.moveaxis(out, 3, 1).reshape(b, s_q, h, d)
    return out.astype(q.dtype)


def ring_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    causal: bool = False,
    scale: Optional[float] = None,
    axis_name: str = "sp",
    use_flash: Optional[bool] = None,
) -> jax.Array:
    """shard_map wrapper: global (B, S, H, D) arrays, S sharded over sp.

    Batch additionally shards over the data axes and heads over tp (when
    divisible), so dp/tp replicas don't redundantly recompute — only the
    sp dimension runs the ring.
    """
    from mlcomp_tpu.parallel.mesh import seq_shard_spec

    b, _, h, _ = q.shape
    h_kv = k.shape[2]
    spec = seq_shard_spec(mesh, b, h, h_kv, axis_name)
    fn = jax.shard_map(
        partial(ring_attention, axis_name=axis_name, causal=causal,
                scale=scale, use_flash=use_flash),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # pallas_call out_shapes carry no varying-mesh-axes metadata, so
        # the vma type check cannot see through the flash-kernel path;
        # the einsum path keeps the check (the specs pin the contract)
        check_vma=not use_flash,
    )
    return fn(q, k, v)
