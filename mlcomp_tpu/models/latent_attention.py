"""Multi-head latent attention: a cache with no head axis.

A token's keys and values, for ALL heads, are projections of one
latent: ``[c ; k_pe] = h W_kva`` (``latent_dim`` + ``rope_dim``
numbers), ``c`` RMS-normed, then a head's ``[k_nope ; v] = c W_kvb``
and its key ``[k_nope ; k_pe]`` (``k_pe`` shared by the heads).  The
cache keeps ``[c ; k_pe]`` a token and nothing a head.

As Kimi-Linear has it, neither ``q`` nor ``k_pe`` is rotated (NoPE: the
model's position information comes from its recurrent layers) and ``q``
is one projection.  What a model may add (LongCat-Flash has all
three): ``rope``, a rotation by position of the shared ``k_pe`` and of
each head's ``q_pe``, ``k_pe`` rotated BEFORE its row is cached so that
the cache holds ``[c ; rotated k_pe]`` and every form below stays what
it is; ``q_rank``, a low-rank query ``q = W_qb norm(W_qa h)``; and the
two scales that go with low ranks, ``q_scale`` on the whole query and
``kv_scale`` on the normed latent (in the cached row: it reaches a
head's keys and values alike).

Attention runs in the latent space, in all three forms: with a head's
key up-projection absorbed into its query, ``q_lat = [W_kvb,k^T q_nope
; q_pe]``, its score against a token is ``q_lat`` times the token's
cached row; its weighted sum of cached ``c`` rows goes through the
head's value up-projection and then ``W_o``.  So no step expands
``heads x (nope + v)`` numbers a cached token.

(a) no cache (``decode=False``) and (b) a chunk against the cache
    (``decode=True``: the chunk's rows written at ``cache_index``
    first): :func:`latent_chunk_attention`, tiled over queries, each
    tile walking the key blocks its queries can see with a running
    softmax, so no (heads, chunk, buffer) score tensor is whole in
    memory and blocks past the chunk's last slot or before the first
    valid one are not read;
(c) one token a row under per-row cursors (``cache_cursor``): the
    kernel ``ops/pallas/latent_attention.py``, which writes the row's
    latent at its cursor and fetches each live block once for keys and
    values alike.  A row whose ``kv_mask`` is all false holds no
    request: nothing of it is read or written.

The cache leaf ``cached_latent`` is (slots, buffer, width) in the
module's ``dtype``: ``buffer`` is the length rounded up to whole blocks
(``ops/pallas/latent_attention.buffer_len``; the slots past the length
lie beyond every window) and ``width`` is ``latent_dim + rope_dim``
rounded up to whole lanes (576 -> 640: a TPU array's minor dimension is
tiled in 128 lanes, so the narrower leaf would occupy as much; the
lanes past 576 are zeros and meet zeros of the query).
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from mlcomp_tpu.models.counts import count_group
from mlcomp_tpu.models.transformer import (
    RMSNorm,
    RopeSpec,
    _window_start,
    apply_rope_spec,
    rmsnorm,
)
from mlcomp_tpu.ops.pallas.latent_attention import (
    LANES,
    NEG_INF,
    block_of,
    blocks_fetched,
    buffer_len,
    latent_decode,
)


def _counts_block(sums, issued):
    if not sums["layer_calls"]:
        return None
    return {
        **sums,
        # of the bytes fetched, the part the windows needed: under 1
        # by the blocks' edges and the leaf's pad lanes
        "tokens_per_fetched_kb": round(
            sums["tokens_attended"] / (sums["bytes_read"] / 1024), 4
        ) if sums["bytes_read"] else None,
    }


# what a call sows into the ``counters`` collection: cached tokens the
# single-token kernel attended (its rows' windows), the bytes of the
# blocks it fetched for them, tokens chunk calls wrote, 1 (the call)
COUNTS = count_group("latent", (
    ("tokens_attended",
     "Cached latent tokens single-token steps attended (each row's "
     "window), summed over layers and steps"),
    ("bytes_read",
     "Bytes of the latent blocks those steps fetched (whole blocks "
     "of ops/pallas/latent_attention.py, each once for keys and "
     "values alike)"),
    ("chunk_tokens",
     "Tokens chunk calls wrote into a latent cache, summed over "
     "layers"),
    ("layer_calls",
     "Latent-attention calls (layers x steps, and chunks)"),
), block=_counts_block)

# queries a tile of the chunk form: 32 heads x 256 x 512 keys x 4 B =
# 16.8 MB of scores a row
Q_TILE = 256


def latent_chunk_attention(q, latents, first_slot, valid, dc, precision=None):
    """``q`` (B, S, H, W): absorbed, scaled queries, query ``t`` at
    slot ``first_slot + t``; ``latents`` (B, L, W), ``L`` whole blocks;
    ``valid`` (B, L) bool.  A query reads the valid slots up to and with
    its own; a cached row's first ``dc`` numbers are its value.  Returns
    the heads' weighted sums (B, S, H, dc) float32."""
    b, s, h, _ = q.shape
    block = block_of(latents.shape[1])
    tq = min(Q_TILE, s)
    tiles = -(-s // tq)
    q = jnp.pad(q, ((0, 0), (0, tiles * tq - s), (0, 0), (0, 0)))
    q = jnp.moveaxis(q.reshape(b, tiles, tq, h, -1), 1, 0)
    first_block = jnp.min(_window_start(valid, b)) // block

    def tile(args):
        qt, t0 = args
        slots_q = first_slot + t0 + jnp.arange(tq)       # (tq,)
        past = jnp.minimum(
            (slots_q[-1] + block) // block, latents.shape[1] // block
        )

        def keys(g, carry):
            m, l, acc = carry
            blk = jax.lax.dynamic_slice_in_dim(latents, g * block, block, 1)
            ok = jax.lax.dynamic_slice_in_dim(valid, g * block, block, 1)
            slots_k = g * block + jnp.arange(block)
            scores = jnp.einsum(
                "bqhw,bkw->bhqk", qt, blk,
                preferred_element_type=jnp.float32, precision=precision,
            )
            seen = ok[:, None, None, :] & (
                slots_k[None, None, None, :] <= slots_q[None, None, :, None]
            )
            scores = jnp.where(seen, scores, NEG_INF)
            m_new = jnp.maximum(m, scores.max(-1))
            p = jnp.where(seen, jnp.exp(scores - m_new[..., None]), 0.0)
            fade = jnp.exp(m - m_new)
            acc = fade[..., None] * acc + jnp.einsum(
                "bhqk,bkc->bhqc", p.astype(blk.dtype), blk[..., :dc],
                preferred_element_type=jnp.float32, precision=precision,
            )
            return m_new, fade * l + p.sum(-1), acc

        m, l, acc = jax.lax.fori_loop(
            jnp.minimum(first_block, past), past, keys, (
                jnp.full((b, h, tq), NEG_INF, jnp.float32),
                jnp.zeros((b, h, tq), jnp.float32),
                jnp.zeros((b, h, tq, dc), jnp.float32),
            ),
        )
        return acc / jnp.where(l == 0.0, 1.0, l)[..., None]

    out = jax.lax.map(tile, (q, jnp.arange(tiles) * tq))  # (T, B, H, tq, dc)
    out = jnp.moveaxis(out, (0, 2), (1, 3)).reshape(b, tiles * tq, h, dc)
    return out[:, :s]


class LatentAttention(nn.Module):
    """Pre-norm latent attention with ``SelfAttention``'s call
    signature: ``q`` (hidden -> heads x (nope + rope); with ``q_rank``
    ``q_a``, hidden -> rank, ``q_norm``, a learned vector over the rank,
    and ``q_b``, rank -> heads x (nope + rope)), ``kv_a`` (hidden ->
    latent + rope), ``kv_norm`` (a learned vector over the latent),
    ``kv_b`` (latent -> heads x (nope + v), the stacked kernel itself:
    both halves are used absorbed) and ``out``.  ``positions`` (B, S)
    are the caller's (a chunk's, a step's per-row ones) and read only
    under ``rope``."""

    hidden: int
    heads: int
    dtype: jnp.dtype
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    latent_dim: int = 512
    rope: Optional[RopeSpec] = None
    q_rank: Optional[int] = None
    q_scale: float = 1.0
    kv_scale: float = 1.0

    @nn.compact
    def __call__(self, x, positions, decode=False, kv_mask=None,
                 cache_cursor=None):
        b, s = x.shape[:2]
        n, dc = self.heads, self.latent_dim
        wide = dc + self.rope_dim
        width = -(-wide // LANES) * LANES
        precision = jax.lax.Precision.HIGHEST \
            if self.dtype == jnp.float32 else None
        h = RMSNorm(self.dtype)(x)
        q_in, q_name = h, "q"
        if self.q_rank:
            with jax.named_scope("mla.q_lora"):
                q_in = rmsnorm(
                    nn.Dense(self.q_rank, use_bias=False, dtype=self.dtype,
                             name="q_a")(h),
                    self.param("q_norm", nn.initializers.ones,
                               (self.q_rank,), jnp.float32),
                    self.dtype,
                )
            q_name = "q_b"
        with jax.named_scope("mla.project"):
            q = nn.DenseGeneral(
                (n, self.nope_dim + self.rope_dim), use_bias=False,
                dtype=self.dtype, name=q_name,
            )(q_in)
            kv = nn.Dense(wide, use_bias=False, dtype=self.dtype,
                          name="kv_a")(h)
            scale = self.param(
                "kv_norm", nn.initializers.ones, (dc,), jnp.float32
            )
            q_pe, k_pe = q[..., self.nope_dim:], kv[..., dc:]
        if self.rope is not None:
            with jax.named_scope("mla.rope"):
                q_pe = apply_rope_spec(q_pe, positions, self.rope)
                k_pe = apply_rope_spec(
                    k_pe[:, :, None], positions, self.rope)[:, :, 0]
        with jax.named_scope("mla.project"):
            latent = jnp.concatenate([
                rmsnorm(kv[..., :dc], scale * self.kv_scale, self.dtype),
                k_pe,
            ], axis=-1)
            w_b = self.param(
                "kv_b", nn.initializers.normal(dc ** -0.5),
                (dc, n, self.nope_dim + self.v_dim), jnp.float32,
            ).astype(self.dtype)
            w_k, w_v = w_b[..., :self.nope_dim], w_b[..., self.nope_dim:]
            q_lat = jnp.concatenate([
                jnp.einsum(
                    "bshd,chd->bshc", q[..., :self.nope_dim], w_k,
                    preferred_element_type=jnp.float32, precision=precision,
                ),
                q_pe.astype(jnp.float32),
            ], axis=-1) * (
                self.q_scale * (self.nope_dim + self.rope_dim) ** -0.5)
            lanes = ((0, 0),) * (q_lat.ndim - 1) + ((0, width - wide),)
            q_lat = jnp.pad(q_lat.astype(self.dtype), lanes)
            latent = jnp.pad(latent, lanes[1:])
        if decode:
            cache = self.variable(
                "cache", "cached_latent", jnp.zeros,
                (b, buffer_len(s), width), self.dtype,
            )
            index = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
            )
        if self.is_initializing():
            # init traces this module at the whole buffer's length only
            # to learn the cache's shapes: the variables exist
            out = jnp.zeros((b, s, n, dc), jnp.float32)
            counts = jnp.zeros(len(COUNTS.entries), jnp.float32)
        elif not decode:
            length = buffer_len(s)
            valid = jnp.ones((b, s), bool) if kv_mask is None \
                else kv_mask[:, :s]
            with jax.named_scope("mla.chunk"):
                out = latent_chunk_attention(
                    q_lat, jnp.pad(latent, ((0, 0), (0, length - s), (0, 0))),
                    0, jnp.pad(valid, ((0, 0), (0, length - s))), dc,
                    precision,
                )
        elif cache_cursor is not None:
            out, counts = self._step(
                q_lat, latent, kv_mask, cache_cursor, cache, dc
            )
        else:
            length = cache.value.shape[1]
            valid = jnp.ones((b, length), bool) if kv_mask is None \
                else jnp.pad(
                    kv_mask, ((0, 0), (0, length - kv_mask.shape[1]))
                )
            i = index.value
            index.value = i + s
            cache.value = jax.lax.dynamic_update_slice(
                cache.value, latent, (0, i, 0)
            )
            with jax.named_scope("mla.chunk"):
                out = latent_chunk_attention(
                    q_lat, cache.value, i, valid, dc, precision
                )
            tokens = jnp.sum(
                jax.lax.dynamic_slice_in_dim(valid, i, s, axis=1)
            ).astype(jnp.float32)
            counts = jnp.stack([
                jnp.float32(0.0), jnp.float32(0.0), tokens,
                jnp.float32(1.0),
            ])
        if decode:
            self.sow(
                "counters", COUNTS.name, counts,
                reduce_fn=lambda a, c: a + c,
                init_fn=lambda: jnp.zeros(len(COUNTS.entries), jnp.float32),
            )
        with jax.named_scope("mla.project"):
            out = jnp.einsum(
                "bshc,chd->bshd", out.astype(self.dtype), w_v,
                preferred_element_type=jnp.float32, precision=precision,
            ).astype(self.dtype)
        return x + nn.DenseGeneral(
            self.hidden, axis=(-2, -1), use_bias=False, dtype=self.dtype,
            name="out",
        )(out)

    def _step(self, q_lat, latent, kv_mask, cache_cursor, cache, dc):
        b, s = q_lat.shape[:2]
        if s != 1:
            raise ValueError(
                "cache_cursor (per-row cursors) is the single-token "
                f"step's contract; got a chunk of {s} tokens"
            )
        stop = jnp.asarray(cache_cursor).astype(jnp.int32) + 1
        # a row with no valid slot starts past any stop: an empty window
        start = _window_start(kv_mask, b)
        with jax.named_scope("mla.decode"):
            out, cache.value = latent_decode(
                q_lat[:, 0], latent[:, 0], cache.value, start, stop, dc=dc
            )
        block = block_of(cache.value.shape[1])
        row_bytes = cache.value.shape[2] * cache.value.dtype.itemsize
        counts = jnp.stack([
            jnp.sum(jnp.maximum(stop - start, 0)).astype(jnp.float32),
            jnp.sum(blocks_fetched(start, stop, block)).astype(jnp.float32)
            * float(block * row_bytes),
            jnp.float32(0.0), jnp.float32(1.0),
        ])
        return out[:, None], counts
