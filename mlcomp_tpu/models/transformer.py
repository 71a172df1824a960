"""Decoder-only Transformer LM — the framework's long-context flagship.

Not present in the upstream reference's model zoo (it predates LLMs); this
is the model family the long-context/distributed machinery (ring attention
over the ``sp`` axis, tensor parallel over ``tp``, pipeline over ``pp``,
MoE over ``ep``) is exercised on, per the build brief's "long-context and
distributed are first-class".

TPU-first: RoPE positions, pre-norm, bfloat16 activations / fp32 residual-
critical params, fused attention via ops.attention, MXU-aligned widths.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from mlcomp_tpu.models import MODELS
from mlcomp_tpu.ops.attention import dot_product_attention


def apply_rope(x: jax.Array, positions: jax.Array, base: float = 10000.0) -> jax.Array:
    """Rotary embeddings; x: (B, S, H, D), positions: (B, S)."""
    d = x.shape[-1]
    half = d // 2
    freq = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freq  # (B, S, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


class RopeSpec(NamedTuple):
    """A layer's rotary embedding, where it is not :func:`apply_rope`'s
    default: the base, how many leading dimensions of each head rotate
    (``rotary_dim``; the rest pass through; 0: nothing rotates, a layer
    without positional embedding), and optionally YaRN's
    frequency blend (``factor`` set): below ``lo`` the published
    frequencies stay, above ``hi`` they are divided by ``factor``,
    with a linear ramp between (``lo`` / ``hi`` from ``beta_fast`` /
    ``beta_slow`` and ``original_max``); cos and sin are both
    multiplied by ``attention_factor``."""

    base: float = 10000.0
    rotary_dim: Optional[int] = None
    factor: Optional[float] = None
    original_max: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    @classmethod
    def of(cls, spec) -> Optional["RopeSpec"]:
        """From a configuration's mapping (None stays None)."""
        if spec is None or isinstance(spec, cls):
            return spec
        return cls(**dict(spec))


def rope_inv_freq(spec: RopeSpec, head_dim: int) -> np.ndarray:
    """The rotary_dim / 2 angular frequencies of ``spec``, float32."""
    d_r = spec.rotary_dim or head_dim
    half = d_r // 2
    j = np.arange(half, dtype=np.float64)
    freq = float(spec.base) ** (-2.0 * j / d_r)
    if spec.factor is not None:
        def dim_of(n_rot):
            return d_r * math.log(
                spec.original_max / (n_rot * 2.0 * math.pi)
            ) / (2.0 * math.log(spec.base))

        lo = max(math.floor(dim_of(spec.beta_fast)), 0)
        hi = min(math.ceil(dim_of(spec.beta_slow)), d_r - 1)
        ramp = np.clip((j - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
        freq = (freq / spec.factor) * ramp + freq * (1.0 - ramp)
    return freq.astype(np.float32)


def apply_rope_spec(x: jax.Array, positions: jax.Array,
                    spec: RopeSpec) -> jax.Array:
    """Rotary embeddings by description; x: (B, S, H, D).  Dimension j
    of the rotating part pairs with j + rotary_dim / 2
    (:func:`apply_rope`'s convention); ``rotary_dim`` 0 rotates nothing."""
    if spec.rotary_dim == 0:
        return x
    d = x.shape[-1]
    d_r = spec.rotary_dim or d
    half = d_r // 2
    angles = positions[..., None].astype(jnp.float32) * rope_inv_freq(spec, d)
    cos = (jnp.cos(angles) * spec.attention_factor)[:, :, None, :]
    sin = (jnp.sin(angles) * spec.attention_factor)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:d_r]
    parts = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    if d_r < d:
        parts.append(x[..., d_r:].astype(jnp.float32))
    return jnp.concatenate(parts, axis=-1).astype(x.dtype)


def resolve_positions(ids: jax.Array, decode: bool, positions):
    """Decode-contract helper shared by the decoder LM families: explicit
    positions are required in decode mode (the caller owns the decode
    cursor — see models/generation.py); otherwise default to 0..S-1."""
    if decode:
        if positions is None:
            raise ValueError(
                "decode=True needs explicit positions (the caller owns "
                "the decode cursor; see models/generation.py)"
            )
        return positions
    if positions is None:
        b, s = ids.shape
        return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    return positions


def rmsnorm(x: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """Functional RMSNorm core (fp32 accumulation) — shared by the module
    below and the stacked-params pipelined LM so the math can't drift."""
    x32 = x.astype(jnp.float32)
    x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + 1e-6)
    return (x32 * scale).astype(dtype)


class RMSNorm(nn.Module):
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        return rmsnorm(x, scale, self.dtype)


def _window_start(kv_mask, b):
    """Per-row first valid cache slot of a LEFT-padded ``kv_mask``
    (B, L) — the int8 kernels' ``kv_start``.  A row with no valid slot
    starts at L, past any stop: its window is empty and the kernels
    neither fetch nor compute it (the engine masks rows that hold no
    request that way)."""
    if kv_mask is None:
        return jnp.zeros((b,), jnp.int32)
    first = jnp.argmax(kv_mask.astype(jnp.int32), axis=1).astype(jnp.int32)
    return jnp.where(jnp.any(kv_mask, axis=1), first, kv_mask.shape[1])


def _row_cursor_dus(buf, upd, cur, seq_axis):
    """Write ``upd[r]`` into ``buf`` at row r's cursor slot(s) —
    per-row ``dynamic_update_slice`` in a ``fori_loop``, NOT a batched
    scatter (a scatter lowering copies the whole buffer; row-wise DUS
    aliases the loop carry in place).  One path still writes this way:
    the bfloat16 cache's per-row-cursor step, which no benchmark cell
    runs.  The int8 cache's single-token step used to, and the chip's
    verdict on that is the ledger's PR 28 and PR 29 lines: a trip costs ~1 us
    whatever the row holds, 2,304 of them a step at 48 slots x 24
    layers, a quarter of the step; ``decode_attention`` now appends
    the token itself (its ``append``).
    ``seq_axis`` is the cache's slot axis (1 for the bf16 (B, L, H, dh)
    layout, 2 for the KV-major quant (B, Hkv, L, dh) layout).  DUS
    CLAMPS at the buffer edge (the engine allocates a scratch slot so
    retired rows' frozen-cursor writes stay in bounds)."""
    def body(r, b_):
        starts = [jnp.int32(0)] * buf.ndim
        starts[0] = r
        starts[seq_axis] = cur[r]
        return jax.lax.dynamic_update_slice(
            b_, jax.lax.dynamic_slice_in_dim(upd, r, 1, 0), tuple(starts)
        )

    return jax.lax.fori_loop(0, buf.shape[0], body, buf)


class SelfAttention(nn.Module):
    """Pre-norm causal self-attention shared by every decoder variant.

    One module so the routing policy (XLA/flash dispatch vs ring attention
    over the ``sp`` axis) lives in exactly one place.
    """

    hidden: int
    heads: int
    kv_heads: int
    dtype: jnp.dtype
    # sequence/context parallelism when the current mesh has an sp axis > 1:
    # True/"ring" = ring attention (sp unbounded, O(S/n) resident);
    # "ulysses" = all-to-all head exchange (sp ≤ kv_heads, denser kernels)
    seq_parallel: "bool | str" = False
    # decode-time int8 KV cache (per-(slot, head) absmax): halves the
    # dominant HBM stream of batched decode; attention runs the Pallas
    # flash-decode kernel (ops/pallas/decode_attention.py).  Training and
    # prefill math are untouched — only the cache storage + its readers.
    kv_quant: bool = False
    # one fused qkv projection instead of three (param path "qkv/kernel",
    # head-axis order [q | k | v]): at decode-GEMV shapes each projection
    # is a separate kernel launch whose per-call cost is visible next to
    # its tiny compute — fusing measured 87.8% vs 77.4% of the weight-
    # bytes roofline per layer (tools sweep, v5e, with the int8 kernel).
    # Param layout changes, so it is an opt-in serving flag; checkpoints
    # convert via fuse_decode_params.
    decode_fused: bool = False
    # what may differ by layer in a model whose layers are not alike;
    # the defaults are the module as it always was (same programs,
    # same parameter paths).  ``head_dim``: a head's width where it is
    # not hidden // heads.  ``rope``: a :class:`RopeSpec` (None:
    # :func:`apply_rope` at its default base; ``rotary_dim`` 0: q and k
    # are not rotated at all).  ``window``: query t
    # attends keys t - window + 1 .. t (None: every earlier key) — a
    # lower bound on the keys, applied in the forward pass, the cache's
    # single-token step and its chunk form; the cache keeps whole
    # buffers and reads their last ``window`` tokens.  ``head_gate``:
    # each head's output is multiplied by the logistic function of its
    # own projection ("head_gate/kernel", hidden -> heads) of the
    # normed layer input.  ``return_normed``: the call returns
    # ``(output, normed input)``, for a layer whose router reads what
    # the attention reads (the norm's parameter stays here).
    # ``qk_norm``: q and k are RMS-normed a head over its channels, each
    # with a learned vector a head width ("q_norm", "k_norm"), BEFORE
    # the rotation: the keys the cache holds are normed and rotated.
    head_dim: Optional[int] = None
    rope: Optional[RopeSpec] = None
    window: Optional[int] = None
    head_gate: bool = False
    return_normed: bool = False
    qk_norm: bool = False

    def _window_lo(self, lo, stop):
        """``lo`` raised to the window's lower bound for keys ending
        (exclusive) at ``stop``; unchanged without a window."""
        if self.window is None:
            return lo
        with jax.named_scope("attn.window"):
            return jnp.maximum(lo, stop - self.window)

    def _band(self, mask, slots, stops):
        """``mask`` without the keys below each query's window:
        ``slots`` are key indices, ``stops`` the queries' exclusive
        stops, both shaped to broadcast against ``mask``."""
        if self.window is None:
            return mask
        with jax.named_scope("attn.window"):
            return mask & (slots >= stops - self.window)

    def _refuse_long_fresh(self, s):
        if self.window is not None and s > self.window \
                and not self.is_initializing():
            raise NotImplementedError(
                f"a fresh prefill of {s} tokens is longer than this "
                f"layer's window of {self.window}: the flash kernel has "
                f"no band; prefill in chunks of at most the window"
            )

    @nn.compact
    def __call__(self, x, positions, decode=False, kv_mask=None,
                 cache_cursor=None):
        h = RMSNorm(self.dtype)(x)
        out = self._attend(x, h, positions, decode, kv_mask, cache_cursor)
        return (out, h) if self.return_normed else out

    def _attend(self, x, h, positions, decode, kv_mask, cache_cursor):
        d_head = self.head_dim or self.hidden // self.heads
        if self.decode_fused:
            qkv = nn.DenseGeneral(
                (self.heads + 2 * self.kv_heads, d_head),
                use_bias=False, dtype=self.dtype, name="qkv",
            )(h)
            q = qkv[..., : self.heads, :]
            k = qkv[..., self.heads : self.heads + self.kv_heads, :]
            v = qkv[..., self.heads + self.kv_heads :, :]
        else:
            q = nn.DenseGeneral((self.heads, d_head), use_bias=False, dtype=self.dtype, name="q")(h)
            k = nn.DenseGeneral((self.kv_heads, d_head), use_bias=False, dtype=self.dtype, name="k")(h)
            v = nn.DenseGeneral((self.kv_heads, d_head), use_bias=False, dtype=self.dtype, name="v")(h)
        if self.qk_norm:
            with jax.named_scope("attn.qk_norm"):
                scale = lambda name: self.param(  # noqa: E731
                    name, nn.initializers.ones, (d_head,), jnp.float32
                )
                q = rmsnorm(q, scale("q_norm"), self.dtype)
                k = rmsnorm(k, scale("k_norm"), self.dtype)
        with jax.named_scope("attn.rope"):
            if self.rope is None:
                q = apply_rope(q, positions)
                k = apply_rope(k, positions)
            else:
                q = apply_rope_spec(q, positions, self.rope)
                k = apply_rope_spec(k, positions, self.rope)
        if self.head_gate:
            gate = jax.nn.sigmoid(nn.Dense(
                self.heads, use_bias=False, dtype=self.dtype,
                name="head_gate",
            )(h))[..., None]
        if decode:
            attn = self._decode_attention(q, k, v, kv_mask, cache_cursor)
            if self.head_gate:
                attn = attn * gate
            return x + nn.DenseGeneral(
                self.hidden, axis=(-2, -1), use_bias=False, dtype=self.dtype, name="out"
            )(attn)
        # GQA: shared KV heads are broadcast inside the attention op, never
        # materialized rep× in HBM
        attn = None
        if self.seq_parallel and self.window is not None:
            raise NotImplementedError(
                "sequence-parallel attention has no window"
            )
        if self.seq_parallel:
            from mlcomp_tpu.parallel.mesh import axis_size, current_mesh
            from mlcomp_tpu.parallel.ring import ring_attention_sharded
            from mlcomp_tpu.parallel.ulysses import ulysses_attention_sharded

            from functools import partial

            mode = (
                "ring" if self.seq_parallel is True else str(self.seq_parallel)
            )
            sp_attn = {
                "ring": ring_attention_sharded,
                # per-block compute through the Pallas flash kernel
                # (parallel/ring.py _ring_flash) — opt-in, see ring.py
                "ring_flash": partial(ring_attention_sharded, use_flash=True),
                "ulysses": ulysses_attention_sharded,
            }
            # validate even when sp == 1, so a typo'd mode fails on the
            # first dev run rather than first pod launch
            if mode not in sp_attn:
                raise ValueError(
                    f"seq_parallel={mode!r}: expected 'ring', 'ring_flash',"
                    f" or 'ulysses'"
                )
            mesh = current_mesh()
            if axis_size(mesh, "sp") > 1:
                attn = sp_attn[mode](q, k, v, mesh, causal=True)
        if attn is None and self.window is not None \
                and q.shape[1] > self.window:
            # longer than the window: the masked XLA path (flash has no
            # band)
            t = jnp.arange(q.shape[1], dtype=jnp.int32)
            mask = self._band(
                t[None, :] <= t[:, None], t[None, :], t[:, None] + 1
            )
            attn = dot_product_attention(q, k, v, mask=mask[None, None])
        if attn is None:
            attn = dot_product_attention(q, k, v, causal=True)
        if self.head_gate:
            attn = attn * gate
        return x + nn.DenseGeneral(
            self.hidden, axis=(-2, -1), use_bias=False, dtype=self.dtype, name="out"
        )(attn)

    def _decode_attention(self, q, k, v, kv_mask, cache_cursor=None):
        """Incremental attention against a KV cache (autoregressive decode).

        The cache buffers are created at init time sized by the init
        input's sequence length (= the generation budget, see
        ``models/generation.py init_cache``); each apply writes the new
        K/V rows at ``cache_index`` and attends q against the whole
        buffer under a slot <= own-slot mask — fixed shapes every step,
        so one compiled program serves the entire decode loop.

        ``kv_mask`` (B, max_len) marks cache slots that are valid keys
        (False = left-padding in a ragged prompt batch).

        ``cache_cursor`` (B,) int32 switches to PER-ROW write offsets:
        each row writes its one token's K/V at its own slot and attends
        slots <= its cursor — the contract the continuous-batching
        engine (mlcomp_tpu/engine.py) drives, where every row is at a
        different decode depth.  It is a single-token contract (s == 1;
        anything wider is a ValueError: chunks run under the one
        ``cache_index``).  The module's scalar ``cache_index`` is
        neither read nor advanced then (the engine owns the cursors).
        """
        if cache_cursor is not None and q.shape[1] != 1:
            raise ValueError(
                "cache_cursor (per-row cursors) is the single-token "
                f"decode step's contract; got a chunk of {q.shape[1]} "
                "tokens (chunked prefill runs under the one cache_index)"
            )
        if self.kv_quant:
            return self._decode_attention_quant(
                q, k, v, kv_mask, cache_cursor
            )
        from mlcomp_tpu.kvpool.attn import current_paged_kv

        ctx = current_paged_kv()
        if ctx is not None:
            # FUSED paged path (engine dispatch core only): K/V live in
            # page arrays, not cache variables — append the new rows
            # into their pages in place and read back through the table
            return self._paged_decode_attention(
                ctx, q, k, v, kv_mask, cache_cursor
            )
        b, s, _, _ = q.shape
        cached_k = self.variable("cache", "cached_key", jnp.zeros, k.shape, k.dtype)
        cached_v = self.variable("cache", "cached_value", jnp.zeros, v.shape, v.dtype)
        index = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
        )
        if cache_cursor is not None:
            # per-row write offsets (s == 1): row b writes slot cur_b
            # and attends slots <= cur_b.  Writes via _row_cursor_dus
            # (per-row DUS, not scatter).
            cur = jnp.asarray(cache_cursor).astype(jnp.int32)
            cached_k.value = _row_cursor_dus(cached_k.value, k, cur, 1)
            cached_v.value = _row_cursor_dus(cached_v.value, v, cur, 1)
            k_all = cached_k.value
            v_all = cached_v.value
            max_len = k_all.shape[1]
            slots = jnp.arange(max_len, dtype=jnp.int32)
            mask = (slots[None, :] <= cur[:, None])[:, None, None]
            mask = self._band(
                mask, slots[None, None, None, :],
                (cur + 1)[:, None, None, None],
            )
            if kv_mask is not None:
                mask = mask & kv_mask[:, None, None, :].astype(jnp.bool_)
            return dot_product_attention(q, k_all, v_all, mask=mask)
        i = index.value
        k_all = jax.lax.dynamic_update_slice(cached_k.value, k, (0, i, 0, 0))
        v_all = jax.lax.dynamic_update_slice(cached_v.value, v, (0, i, 0, 0))
        cached_k.value = k_all
        cached_v.value = v_all
        index.value = i + s
        max_len = k_all.shape[1]
        slots = jnp.arange(max_len, dtype=jnp.int32)
        q_slots = i + jnp.arange(s, dtype=jnp.int32)
        mask = (slots[None, :] <= q_slots[:, None])[None, None]  # (1,1,S,max)
        mask = self._band(
            mask, slots[None, None, None, :],
            q_slots[None, None, :, None] + 1,
        )
        if kv_mask is not None:
            mask = mask & kv_mask[:, None, None, :].astype(jnp.bool_)
        if s > 1:
            self._refuse_long_fresh(s)
            # prefill fast path: when the cache is still empty, attention
            # over the full buffer under the slot mask equals plain causal
            # attention over just the new K/V — which takes the flash
            # kernel (dense masks don't).  Ragged LEFT-padded batches
            # stay on the kernel too: the pad prefix becomes a per-row
            # kv_start window (pad QUERY rows get garbage outputs that
            # generation discards — their real attention output is
            # never read).  lax.cond keeps chunked prefill (i > 0) on
            # the general path.
            if kv_mask is None:
                fresh = lambda: dot_product_attention(q, k, v, causal=True)
            else:
                start = jnp.argmax(
                    kv_mask[:, :s].astype(jnp.int32), axis=1
                ).astype(jnp.int32)
                fresh = lambda: dot_product_attention(
                    q, k, v, causal=True, kv_start=start
                )
            return jax.lax.cond(
                i == 0,
                fresh,
                lambda: dot_product_attention(q, k_all, v_all, mask=mask),
            )
        return dot_product_attention(q, k_all, v_all, mask=mask)

    def _paged_decode_attention(self, ctx, q, k, v, kv_mask, cache_cursor):
        """Fused paged decode for the bf16/f32 cache family
        (``kvpool/attn.PagedKV`` installed by the engine's dispatch
        core): the new K/V rows scatter into their physical pages in
        place (table-routed — retired rows land on GRAVE), and the
        attention reads a per-layer table gather whose bytes equal the
        dense buffer's, so the mask math below is the cursor branch of
        :meth:`_decode_attention` verbatim.  No dense cache variable is
        ever created — the dense view exists only transiently inside
        this layer's attention consumer."""
        self._refuse_paged_window()
        if cache_cursor is None:
            raise NotImplementedError(
                "fused paged attention runs only under the engine's "
                "per-row-cursor decode dispatch (admission prefills "
                "carry a dense (1, l_buf) cache)"
            )
        b = k.shape[0]
        prefix = "/".join(self.path)
        k_i = ctx.index_of(prefix, "cached_key")
        v_i = ctx.index_of(prefix, "cached_value")
        cur = jnp.asarray(cache_cursor).astype(jnp.int32)
        rows = jnp.arange(b, dtype=jnp.int32)
        ctx.append_rows(k_i, rows, cur, k[:, 0])
        ctx.append_rows(v_i, rows, cur, v[:, 0])
        k_all = ctx.gather_dense(k_i)          # (B, L, Hkv, dh)
        v_all = ctx.gather_dense(v_i)
        max_len = k_all.shape[1]
        slots = jnp.arange(max_len, dtype=jnp.int32)
        mask = (slots[None, :] <= cur[:, None])[:, None, None]
        if kv_mask is not None:
            mask = mask & kv_mask[:, None, None, :].astype(jnp.bool_)
        return dot_product_attention(q, k_all, v_all, mask=mask)

    def _refuse_paged_window(self):
        if self.window is not None:
            raise NotImplementedError(
                "the paged attention kernels take no window: serve a "
                "model with window layers with kv_layout='dense'"
            )

    def _paged_decode_attention_quant(self, ctx, q, k, v, kv_mask,
                                      cache_cursor):
        """Fused paged decode for the int8 KV family: quantize the new
        rows exactly as the dense path would, scatter values AND scales
        into their pages in place, then attend THROUGH the page table —
        the paged Pallas kernels when the geometry keeps the dense
        block partition (``paged_block_kv``), else a per-layer lax
        gather feeding the DENSE kernels.  Both routes are bit-identical
        to the dense engine: the kernels share ``_flash_block_update``
        and the block partition; the gather is pure data movement."""
        from mlcomp_tpu.ops.pallas.decode_attention import (
            decode_attention,
            paged_decode_attention,
            quantize_kv,
        )

        self._refuse_paged_window()
        if cache_cursor is None:
            raise NotImplementedError(
                "fused paged attention runs only under the engine's "
                "per-row-cursor decode dispatch (admission prefills "
                "carry a dense (1, l_buf) cache)"
            )
        b, _, hkv, dh = k.shape
        dhp = -(-dh // 128) * 128
        prefix = "/".join(self.path)
        kq_i = ctx.index_of(prefix, "cached_key_q")
        ks_i = ctx.index_of(prefix, "cached_key_scale")
        vq_i = ctx.index_of(prefix, "cached_value_q")
        vs_i = ctx.index_of(prefix, "cached_value_scale")

        if dhp != dh:
            kp = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, dhp - dh)))
            vp = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dhp - dh)))
        else:
            kp, vp = k, v
        kq, ks_ = quantize_kv(kp)              # (B, 1, Hkv, dhp) / (B, 1, Hkv)
        vq, vs_ = quantize_kv(vp)
        cur = jnp.asarray(cache_cursor).astype(jnp.int32)
        rows = jnp.arange(b, dtype=jnp.int32)
        sdt = ctx.spec(ks_i).dtype
        ctx.append_rows(kq_i, rows, cur, kq[:, 0])
        ctx.append_rows(vq_i, rows, cur, vq[:, 0])
        ctx.append_rows(ks_i, rows, cur, ks_[:, 0, :, None].astype(sdt))
        ctx.append_rows(vs_i, rows, cur, vs_[:, 0, :, None].astype(sdt))

        row_start = _window_start(kv_mask, b)
        qp = (
            jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, dhp - dh)))
            if dhp != dh else q
        )
        scale = 1.0 / (dh**0.5)
        if ctx.use_pallas_kernels(kq_i, hkv, dhp):
            tbl = ctx.kernel_table(kq_i)
            pages = (ctx.pages[kq_i], ctx.pages[ks_i],
                     ctx.pages[vq_i], ctx.pages[vs_i])
            out = paged_decode_attention(
                qp[:, 0], *pages, tbl, kv_start=row_start,
                kv_stop=cur + 1, scale=scale,
            )
            return out[..., :dh][:, None]
        # gather fallback (geometry cannot keep the dense block
        # partition): per-layer lax reads feeding the DENSE kernels —
        # same bytes, same math, still no carried dense view
        k8 = ctx.gather_dense(kq_i)
        ks4 = ctx.gather_dense(ks_i)
        v8 = ctx.gather_dense(vq_i)
        vs4 = ctx.gather_dense(vs_i)
        out = decode_attention(
            qp[:, 0], k8, ks4, v8, vs4, kv_start=row_start,
            kv_stop=cur + 1, scale=scale,
        )
        return out[..., :dh][:, None]

    def _decode_attention_quant(self, q, k, v, kv_mask, cache_cursor=None):
        """int8 KV-cache decode (``kv_quant=True``).

        Cache layout is (B, Hkv, L, dh) int8 + (B, Hkv, 1, L) bf16
        scales (bf16 storage halves the dominant masked full-buffer
        scale rewrite; scales are still COMPUTED in f32 and the
        flash-decode kernel upcasts in VMEM — round-5 glue attack) —
        KV-major so the flash-decode kernel walks contiguous tiles; L is
        lane-rounded at allocation (extra slots sit beyond ``kv_stop``,
        masked for free) and dh zero-pads to a lane multiple (pads add 0
        to every logit and produce discarded output columns).

        Single-token steps run ops/pallas/decode_attention.py with
        per-row [kv_start, i+1) windows (LEFT-pad contract from
        models/generation.py: invalid slots are a prefix, so
        ``kv_start = argmax(kv_mask)`` is exact).  Prefill attends the
        fresh bf16 K/V directly — ragged batches stay on the flash
        kernel via ``kv_start`` windows instead of dropping to a dense
        mask like the bf16 cache path.  Chunked decode (i > 0, s > 1:
        chunked prefill under the one ``cache_index``) runs the
        multi-query flash kernel where ``chunk_uses_kernels`` says so
        (``decode_attention_chunk`` — one int8 cache sweep for all s
        queries); other widths off the TPU and mesh serving dequantize
        the buffer in XLA — correct, bandwidth-amortized at prefill
        widths.
        """
        from mlcomp_tpu.kvpool.attn import current_paged_kv
        from mlcomp_tpu.ops.pallas.decode_attention import (
            decode_attention,
            pick_buffer_len,
            quantize_kv,
        )

        ctx = current_paged_kv()
        if ctx is not None:
            # FUSED paged path (engine dispatch core only): no dense
            # cache variables — pages, table-routed writes, and the
            # paged kernel family replace the buffers below
            return self._paged_decode_attention_quant(
                ctx, q, k, v, kv_mask, cache_cursor
            )

        b, s, hkv, dh = k.shape
        dhp = -(-dh // 128) * 128
        # at init time s == the full buffer length (init_cache contract);
        # the buffer length must leave the flash-decode kernel a FAT
        # block size (pick_buffer_len) — a plain 128-round can land on
        # lengths like 2176 = 128 x 17 with no mid-size divisor
        lpad = pick_buffer_len(s, hkv, dhp)

        def zeros(shape, dt):
            return lambda: jnp.zeros(shape, dt)

        ckq = self.variable(
            "cache", "cached_key_q", zeros((b, hkv, lpad, dhp), jnp.int8)
        )
        # scale caches store bf16 (round 5): the per-step masked scale
        # write rewrites the WHOLE (B, Hkv, 1, L) buffer (a lane-minor
        # dynamic index makes one-slot DUS a full relayout copy — the
        # r4 A/B), so its bytes are pure per-token overhead; bf16
        # halves them.  Quantization still computes the scale in f32
        # (exact division), only the stored dequant multiplier rounds —
        # a ~0.2% relative perturbation on top of int8's ~0.8% step
        # (the benchmark's ``correct`` check holds the outputs to the
        # plain reference).
        cks = self.variable(
            "cache", "cached_key_scale", zeros((b, hkv, 1, lpad), jnp.bfloat16)
        )
        cvq = self.variable(
            "cache", "cached_value_q", zeros((b, hkv, lpad, dhp), jnp.int8)
        )
        cvs = self.variable(
            "cache", "cached_value_scale", zeros((b, hkv, 1, lpad), jnp.bfloat16)
        )
        index = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
        )
        if self.is_initializing():
            # init_cache traces this module at s == the whole buffer
            # only to learn the cache SHAPES.  Attending that
            # buffer-wide "chunk" would trace ceil(L/32) kernel tiles
            # per layer on a TPU backend (the wide-chunk route) — tens
            # of seconds per init_cache call at 16 layers, a cost the
            # CPU route never shows.  The variables exist; that is all
            # init needs.
            return jnp.zeros_like(q)
        i = index.value
        l_buf = ckq.value.shape[2]

        if dhp != dh:
            kp = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, dhp - dh)))
            vp = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dhp - dh)))
        else:
            kp, vp = k, v
        kq, ks_ = quantize_kv(kp)
        vq, vs_ = quantize_kv(vp)

        def flash(kv_start, kv_stop, append=False):
            """Single-token flash-decode against the updated buffers,
            mesh-dispatched (a bare pallas_call would not partition
            itself under SPMD) — shared by the global-cursor and
            per-row-cursor (engine) paths.  With ``append`` the token
            is not in the buffers yet: the kernel writes it at each
            row's ``kv_stop - 1``, in place, on its way through the
            row's last granule, and the cache variables take what it
            returns.  The softmax scale uses the TRUE head dim (q was
            zero-padded to a lane multiple)."""
            from mlcomp_tpu.ops.quant import pallas_mesh

            qp = (
                jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, dhp - dh)))
                if dhp != dh else q
            )
            caches = (ckq, cks, cvq, cvs)
            kw = dict(kv_start=kv_start, kv_stop=kv_stop,
                      scale=1.0 / (dh**0.5))
            if append:
                kw["append"] = (kq[:, 0], ks_[:, 0], vq[:, 0], vs_[:, 0])
            mesh = pallas_mesh()
            if mesh is not None:
                from mlcomp_tpu.ops.pallas.decode_attention import (
                    sharded_decode_attention,
                )

                out = sharded_decode_attention(
                    qp[:, 0], *(c.value for c in caches), mesh, **kw
                )
            else:
                out = decode_attention(
                    qp[:, 0], *(c.value for c in caches), **kw
                )
            if append:
                out, *written = out
                for c, value in zip(caches, written):
                    c.value = value
            return out[..., :dh][:, None]

        def chunk_attend(row_start, stop0):
            """s>1 attention against the just-updated quant cache with
            per-row per-query causal stops [row_start, stop0 + j):
            the multi-query flash kernel when eligible (ONE int8 cache
            sweep for all s queries), the XLA dequant path otherwise
            (wide prefill chunks off the TPU, mesh serving)."""
            from mlcomp_tpu.ops.pallas.decode_attention import (
                chunk_uses_kernels,
                decode_attention_chunk,
            )
            from mlcomp_tpu.ops.quant import pallas_mesh

            # chunks up to CHUNK_MAX_SQ always ride the kernel; WIDE
            # chunks (admission prefill) ride the query-TILED kernel
            # sweeps when wide_chunk_mode says so (TPU default) instead
            # of round-tripping a full bf16 copy of the cache per layer.
            if chunk_uses_kernels(s, mesh=pallas_mesh() is not None):
                qp = (
                    jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, dhp - dh)))
                    if dhp != dh else q
                )
                out = decode_attention_chunk(
                    qp, ckq.value, cks.value, cvq.value, cvs.value,
                    kv_start=row_start, kv_stop0=stop0,
                    scale=1.0 / (dh**0.5), window=self.window,
                )
                return out[..., :dh]
            k_scale = cks.value.transpose(0, 1, 3, 2)   # (B, Hkv, L, 1)
            v_scale = cvs.value.transpose(0, 1, 3, 2)
            k_all = (
                ckq.value.astype(jnp.float32) * k_scale
            ).astype(k.dtype).transpose(0, 2, 1, 3)[..., :dh]
            v_all = (
                cvq.value.astype(jnp.float32) * v_scale
            ).astype(v.dtype).transpose(0, 2, 1, 3)[..., :dh]
            slots = jnp.arange(l_buf, dtype=jnp.int32)
            stops = stop0[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
            mask = slots[None, None, None, :] < stops[:, None, :, None]
            mask = mask & (
                slots[None, :] >= row_start[:, None]
            )[:, None, None, :]
            mask = self._band(
                mask, slots[None, None, None, :], stops[:, None, :, None]
            )
            return dot_product_attention(q, k_all, v_all, mask=mask)

        if cache_cursor is not None:
            # per-row cursors (engine contract, see _decode_attention):
            # each row's K/V go to its own slot(s), window per row.
            cur = jnp.asarray(cache_cursor).astype(jnp.int32)
            row_start = _window_start(kv_mask, b)
            # the step every engine dispatch takes: the kernel appends
            # the token where it attends it, for the rows that hold a
            # window and for no other
            return flash(
                self._window_lo(row_start, cur + 1), cur + 1,
                append=True,
            )
        if s == 1:
            # single-token step under ONE cursor (bare ``generate``;
            # no cell runs it): every row writes the same slot, so one
            # update-slice a tensor, and a masked select for the
            # lane-minor scale caches
            ckq.value = jax.lax.dynamic_update_slice(
                ckq.value, kq.reshape(b, hkv, 1, dhp), (0, 0, i, 0)
            )
            cvq.value = jax.lax.dynamic_update_slice(
                cvq.value, vq.reshape(b, hkv, 1, dhp), (0, 0, i, 0)
            )
            sdt = cks.value.dtype
            hit = (
                jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, l_buf), 3)
                == i
            )
            cks.value = jnp.where(
                hit, ks_.reshape(b, hkv, 1, 1).astype(sdt), cks.value
            )
            cvs.value = jnp.where(
                hit, vs_.reshape(b, hkv, 1, 1).astype(sdt), cvs.value
            )
        else:
            sdt = cks.value.dtype
            ckq.value = jax.lax.dynamic_update_slice(
                ckq.value, kq.transpose(0, 2, 1, 3), (0, 0, i, 0)
            )
            cks.value = jax.lax.dynamic_update_slice(
                cks.value,
                ks_.transpose(0, 2, 1)[:, :, None].astype(sdt), (0, 0, 0, i)
            )
            cvq.value = jax.lax.dynamic_update_slice(
                cvq.value, vq.transpose(0, 2, 1, 3), (0, 0, i, 0)
            )
            cvs.value = jax.lax.dynamic_update_slice(
                cvs.value,
                vs_.transpose(0, 2, 1)[:, :, None].astype(sdt), (0, 0, 0, i)
            )
        index.value = i + s

        start = _window_start(kv_mask, b)

        if s == 1:
            return flash(self._window_lo(start, i + 1), i + 1)
        self._refuse_long_fresh(s)

        def fresh_prefill():
            if kv_mask is None:
                return dot_product_attention(q, k, v, causal=True)
            return dot_product_attention(q, k, v, causal=True, kv_start=start)

        def chunked():
            # the per-query stop is the same for every row here (global
            # cache_index); chunk_attend routes the multi-query kernel
            # vs XLA dequant
            return chunk_attend(start, jnp.broadcast_to(i + 1, (b,)))

        return jax.lax.cond(i == 0, fresh_prefill, chunked)


class DecoderLayer(nn.Module):
    hidden: int
    heads: int
    kv_heads: int
    mlp_dim: int
    dtype: jnp.dtype
    seq_parallel: "bool | str" = False
    kv_quant: bool = False
    decode_fused: bool = False

    @nn.compact
    def __call__(self, x, positions, decode=False, kv_mask=None,
                 cache_cursor=None):
        x = SelfAttention(
            self.hidden, self.heads, self.kv_heads, self.dtype,
            seq_parallel=self.seq_parallel, kv_quant=self.kv_quant,
            decode_fused=self.decode_fused, name="attn",
        )(x, positions, decode=decode, kv_mask=kv_mask,
          cache_cursor=cache_cursor)
        h = RMSNorm(self.dtype)(x)
        if self.decode_fused:
            # fused [gate | up] projection: same per-call-overhead
            # argument as the qkv fusion above
            gu = nn.Dense(
                2 * self.mlp_dim, use_bias=False, dtype=self.dtype,
                name="gate_up",
            )(h)
            gate, up = gu[..., : self.mlp_dim], gu[..., self.mlp_dim:]
        else:
            gate = nn.Dense(self.mlp_dim, use_bias=False, dtype=self.dtype, name="gate")(h)
            up = nn.Dense(self.mlp_dim, use_bias=False, dtype=self.dtype, name="up")(h)
        h = nn.silu(gate) * up
        return x + nn.Dense(self.hidden, use_bias=False, dtype=self.dtype, name="down")(h)


class _LMHead(nn.Module):
    """fp32 logits head with an accessible kernel.

    Setup-style (not compact) so the fused-loss path can read the kernel
    without applying the matmul; the param lands at ``<name>/kernel`` —
    byte-identical layout to the ``nn.Dense(name=...)`` it replaces, so
    checkpoints interchange between fused and plain configs."""

    vocab_size: int
    hidden: int
    # matmul compute dtype: fp32 params always; "bfloat16" runs the MXU
    # at full rate with fp32 ACCUMULATION (logits stay f32) at bf16
    # mantissa cost on inputs — the standard LM-head trade on TPU
    compute_dtype: str = "float32"
    # Dense-equivalent semantics (y = x @ kernel, no bias): advertise to
    # ops/quant.py's method interception so int8 decoding routes this
    # module through the Pallas kernel like the Dense it replaced;
    # dtype keeps the intercepted output fp32 like the plain path
    quant_kernel_eligible = True
    dtype = jnp.float32

    def setup(self):
        self.kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (self.hidden, self.vocab_size),
            jnp.float32,
        )

    def __call__(self, h):
        ct = jnp.dtype(self.compute_dtype)
        return jax.lax.dot_general(
            h.astype(ct), self.kernel.astype(ct),
            (((h.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def get_kernel(self):
        return self.kernel


def _cat_kernels(leaves, axis):
    """Concatenate projection kernels along their output axis — raw
    arrays or int8-quantized {"q8", "q8_scale"} leaves (per-output-
    channel scales concatenate to exactly what quantizing the
    concatenated weight would produce)."""
    from mlcomp_tpu.ops.quant import is_quantized_leaf

    if all(is_quantized_leaf(l) for l in leaves):
        return {
            "q8": jnp.concatenate([l["q8"] for l in leaves], axis),
            "q8_scale": jnp.concatenate([l["q8_scale"] for l in leaves], axis),
        }
    if any(is_quantized_leaf(l) for l in leaves):
        raise ValueError("cannot fuse a mix of quantized and raw kernels")
    return jnp.concatenate(leaves, axis)


def fuse_decode_params(params):
    """Convert a standard decoder params tree to the ``decode_fused``
    layout: every ``q``/``k``/``v`` sibling trio fuses to ``qkv``
    (head-axis concat, order [q | k | v]) and every ``gate``/``up`` pair
    to ``gate_up`` ([gate | up]).  Accepts raw or int8-quantized trees
    (before or after ``ops.quant.quantize_params`` — the results are
    identical).  Anything else passes through untouched, so the
    transform is safe on models without these modules."""
    from mlcomp_tpu.ops.quant import is_quantized_leaf

    def fusable(node, names):
        # exactly {"kernel"}: a bias (or any other sibling param) has no
        # slot in the fused module — dropping it silently would corrupt
        # the model, so such trios pass through unfused
        return all(
            isinstance(node.get(n), dict) and set(node[n]) == {"kernel"}
            for n in names
        )

    def visit(node):
        if not isinstance(node, dict) or is_quantized_leaf(node):
            return node
        node = {k: visit(v) for k, v in node.items()}
        if fusable(node, ("q", "k", "v")):
            kernels = [node.pop(n)["kernel"] for n in ("q", "k", "v")]
            node["qkv"] = {"kernel": _cat_kernels(kernels, 1)}
        if fusable(node, ("gate", "up")):
            kernels = [node.pop(n)["kernel"] for n in ("gate", "up")]
            node["gate_up"] = {"kernel": _cat_kernels(kernels, 1)}
        return node

    return visit(dict(params))


@MODELS.register("transformer_lm")
class TransformerLM(nn.Module):
    vocab_size: int = 32000
    hidden: int = 512
    layers: int = 8
    heads: int = 8
    kv_heads: Optional[int] = None
    mlp_dim: Optional[int] = None
    dtype: str = "bfloat16"
    seq_parallel: "bool | str" = False
    # rematerialize each decoder layer in the backward pass: activation
    # memory drops from O(layers * S * hidden * ~10 tensors) to the
    # layer's input and, where attention takes the flash kernel, that
    # kernel's five residuals: q, k, v as they enter it, its output and
    # one float32 logsumexp a row (four hidden-wide tensors a token with
    # the input, at kv_heads = heads / 2: k and v make one between them).
    # Done twice: both norms, the o / gate / up projections and the
    # SwiGLU product (~1/5 extra matmul FLOPs).  Done once: the q / k / v
    # projections, RoPE, the transposes and the flash forward kernel,
    # whose S^2 work costs far more time a byte kept than any matmul.
    # Below the kernel's lengths (ops/attention.py _flash_covers) no name
    # is bound and the whole layer is recomputed
    remat: bool = False
    # compute the next-token CE inside the model via the chunked fused
    # head (ops/fused_ce.py) instead of materializing (B, S, V) fp32
    # logits: outputs become per-token losses (B, S) whenever decode is
    # False — pair with ``loss: lm_cross_entropy_fused`` and per-token
    # metrics off.  Decode/generation still produces logits.
    fused_loss: bool = False
    fused_loss_chunk: int = 512
    # lm_head matmul compute dtype.  Measured NEUTRAL on v5e (44.4k vs
    # 44.1k tok/s at 268M — XLA already runs fp32 matmuls at bf16-pass
    # rate under --xla_allow_excess_precision); kept as a knob for
    # platforms where fp32 matmul really is slower
    head_dtype: str = "float32"
    # int8 KV cache for decode (see SelfAttention.kv_quant): halves the
    # KV HBM stream that dominates batched/long-context serving.
    # Config: ``kv_quant: true`` in the model mapping (or ``--kv-quant``
    # on the serve CLI); training ignores it.
    kv_quant: bool = False
    # fused qkv + gate_up projections (serving): fewer, fatter decode
    # GEMV kernel calls (see SelfAttention.decode_fused).  Param paths
    # change ("qkv", "gate_up") — convert standard checkpoints with
    # fuse_decode_params; outputs are bit-identical (the fused matmul
    # computes each output column from the same contraction in the same
    # block order).
    decode_fused: bool = False
    # every RMSNorm output in this model feeds dense-like intercepted
    # projections (qkv / q,k,v / gate_up / gate,up / lm_head), so
    # ops/quant's fold_norms decode optimization is safe here — the
    # norm computes inside the consuming Pallas kernel's prologue.
    # (MoE variants keep this off: their norms also feed router/expert
    # einsums the interceptor never sees.)
    fold_norms_eligible = True

    @nn.compact
    def __call__(
        self,
        x,
        train: bool = False,
        decode: bool = False,
        positions=None,
        kv_mask=None,
        cache_cursor=None,
        last_logits_only: bool = False,
    ):
        """Forward pass.  ``decode=True`` switches to incremental decoding
        against a mutable "cache" collection (see models/generation.py);
        ``positions`` (required then) carries each token's absolute RoPE
        position, and ``kv_mask`` (B, max_len) masks out invalid
        (left-pad) cache slots.  ``cache_cursor`` (B,) int32 selects
        per-row cache write offsets for single-token steps (the
        continuous-batching engine's contract, see SelfAttention).
        ``last_logits_only`` (static) is a prefill's caller saying it
        keeps the last position's logits alone: every layer still sees
        the whole sequence (the cache is written as ever), but the
        final norm and the head run on ``h[:, -1:]`` and the result is
        (B, 1, vocab) — XLA does not narrow a (B, S, vocab) product to
        the row a caller slices from it."""
        dtype = jnp.dtype(self.dtype)
        ids = x.astype(jnp.int32)
        positions = resolve_positions(ids, decode, positions)
        kv_heads = self.kv_heads or self.heads
        mlp_dim = self.mlp_dim or self.hidden * 4

        h = nn.Embed(self.vocab_size, self.hidden, dtype=dtype, name="emb")(ids)
        layer_cls = DecoderLayer
        if self.remat and not decode:
            from mlcomp_tpu.ops.pallas import flash_attention

            # static_argnums counts self as 0: decode is arg 3.  The
            # policy keeps what the flash kernel's backward reads (no
            # name is bound where attention takes the XLA path)
            layer_cls = nn.remat(
                DecoderLayer, static_argnums=(3,),
                policy=jax.checkpoint_policies.save_only_these_names(
                    *flash_attention.REMAT_SAVED_NAMES
                ),
            )
        for i in range(self.layers):
            # explicit names keep param paths identical with and without
            # remat (nn.remat would auto-name "CheckpointDecoderLayer_i",
            # breaking checkpoint interchange between the two modes)
            h = layer_cls(
                self.hidden, self.heads, kv_heads, mlp_dim, dtype,
                seq_parallel=self.seq_parallel, kv_quant=self.kv_quant,
                decode_fused=self.decode_fused,
                name=f"DecoderLayer_{i}",
            )(h, positions, decode, kv_mask, cache_cursor)
        if last_logits_only:
            h = h[:, -1:]
        h = RMSNorm(dtype)(h)
        head = _LMHead(
            self.vocab_size, self.hidden, compute_dtype=self.head_dtype,
            name="lm_head",
        )
        if self.fused_loss and not decode:
            from mlcomp_tpu.ops.fused_ce import fused_linear_cross_entropy

            # next-token CE computed chunk-wise against the (known)
            # shifted input; the final position has no target — its
            # label is a dummy and the loss fn drops it
            labels = jnp.concatenate(
                [ids[:, 1:], jnp.zeros((ids.shape[0], 1), jnp.int32)], axis=1
            )
            # largest divisor of S that fits the configured chunk, so any
            # sequence length works (chunking is a memory knob, not a
            # shape contract)
            s_len = h.shape[1]
            chunk = min(self.fused_loss_chunk, s_len)
            while s_len % chunk:
                chunk -= 1
            return fused_linear_cross_entropy(
                h, head.get_kernel(), labels, chunk
            )
        return head(h)
