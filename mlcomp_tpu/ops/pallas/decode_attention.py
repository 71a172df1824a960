"""Pallas TPU flash-decode over an int8-quantized KV cache.

At serving batch sizes the decode step is KV-bandwidth-bound: every new
token re-reads the whole (B, L, Hkv, dh) cache while computing a single
query row per sequence (at B=8 / S=2304 the bf16 KV read is ~2.4
GB/step by its shapes, more than the weights).  Storing
the cache int8 halves those bytes, but only if the dequantize happens
after the block is already in VMEM — the same argument as
quant_matmul.py, applied to the other big decode tensor.  XLA cannot:
a jnp ``k8 * ks`` prefix materializes the bf16 copy in HBM every step
(1x int8 read + 2x write + 2x read = worse than plain bf16).

    out[b, h, :] = softmax(q[b, h, :] @ K[b, hkv, j, :] * ks[b, hkv, j])
                   @ (V * vs)            over valid slots j

- K rows are quantized per (slot, kv-head) with absmax/127 scales, so
  the K scale commutes with the q·k contraction and multiplies the
  (G, BLK) logit block, not the (BLK, dh) keys; the V scale folds into
  the probability row before the p@V matmul.  Dequantization never
  touches HBM.
- cache layout (B, Hkv, L, dh) / scales (B, Hkv, 1, L); ALL KV heads
  of a stretch of tokens ride one batched dot_general.  A single query
  row makes every matmul tiny, so per-step overhead, not bandwidth, is
  the design constraint at decode shapes: the first cut ran a
  (B, Hkv, L/BLK) grid and lost to XLA on grid steps alone (another
  toolchain, another shape; not measured on this chip).  Online softmax
  (m, l, acc VMEM scratch) carries across a row's steps — the flash
  recipe with a single query block.
- GQA: the G = H/Hkv query heads of a group ride the sublane axis of
  one (G, dh) block (padded to 8 sublanes), so shared KV heads are
  read once per group, never replicated.
- valid-slot masking via scalar-prefetched per-row windows
  [kv_start, kv_stop): generation's LEFT-padded ragged prompts make
  invalid slots a prefix, so a window is exact (models/generation.py
  contract), and because kv_stop is the decode cursor the
  not-yet-generated tail of the buffer costs nothing.
- the single-token kernel (``decode_attention``) WALKS the window: the
  cache stays in HBM, one grid step holds a block of rows' queries and
  outputs, and a loop a row brings the window's granules
  (``auto_block_kv`` tokens each) through a double buffer — granule i+1, or the
  next live row's first, lands while granule i computes.  A trip moves
  and attends only the 128-token lane blocks of its granule that the
  window touches (PR 36): a granule inside the window goes whole, the
  window's first and last are trimmed to the smallest width of a short
  static ladder (``fetch_ladder``; copy sizes are static) that covers
  their live blocks, so a thin window in a fat granule (a
  ``batch-offline`` row holds ~240 live tokens of a 640-token granule)
  is not paid for at the granule's width, and a long one runs whole
  trips as before.  A row whose window is empty (the engine hands one
  to every slot that holds no request) starts no copy and computes
  nothing.  The engine's step also WRITES through it (``append``,
  PR 29): the row's new token is patched into its last trip's columns
  in VMEM, attended there, and the tile that holds it copied back in
  place, so the cache takes one device operation a layer whose cost
  follows the live rows, where a loop of update-slices over every slot
  row was a quarter of the step (ledger, PR 28 against PR 29).  The
  multi-query chunk kernel and the paged kernel still sweep a
  (B, L/BLK) BlockSpec grid of fat blocks (``KV_BLOCK_BUDGET``): blocks
  outside a row's window are clamped in the index maps to the nearest
  live block of that row, so the pipeline elides the copy, and their
  compute is pl.when-skipped.

Measured on one v5e through the benchmark (InternLM2-1.8B serve cells:
B 48, H 16, Hkv 8, dh 128, L 2560; ``kv8_decode_attn_roofline`` counts
the LIVE keys and values): the (B, L/640) sweep this kernel replaced
read 12.3% of that roofline in ``batch-offline`` and took the same
~205-215 us a layer call with 10 of 48 rows live as with all 48
(ledger, PR 24 and PR 25).  This kernel's readings, the granule sweep
behind ``KV_BLOCK_BUDGET`` and what a trip costs: PERF.md, PR 26 and
PR 36.

The upstream reference has no decode path at all (its infer stage is a
batch forward); this kernel is part of the serving surface the TPU
build adds on top of it.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mlcomp_tpu.ops.pallas import interpret_default, on_tpu

NEG_INF = -1e30
LANES = 128
SUBLANES = 8

# K+V bytes of one block: a grid step of the BlockSpec-swept kernels
# (chunk and paged) and one GRANULE of the single-token kernel's walk,
# single-buffered.  For the walk, measured on one v5e with ``append``
# and trips trimmed to their windows' lane blocks (tools/exp_decattn.py;
# PERF.md, PR 36), us a layer call at granules of 128 / 256 / 512 / 640
# tokens, B 48 / Hkv 8 / L 2560: 50.7 / 41.4 / 38.5 / 34.1 at 10 live
# rows of ~560 tokens, 118.9 / 104.4 / 86.5 / 86.2 at 48 rows of ~240,
# and 702 / 488 / 380 / 373 over the whole buffer; at Hkv 16 / L 2304,
# whole buffer, 128 / 256 / 384 / 768 took 153 / 119 / 111 / 109.  A
# trip costs ~1.3 us whatever it moves (eight copies started and waited
# for, the flash update's fixed part, the appended tile) and ~1.5 ns a
# token at 128-384 tokens, 1.94 us whole at 640 (copies alone 1.81,
# flash update alone 1.40): thin granules lose on trips what they save
# on a window's edges, and a fat granule's edges are trimmed by the
# ladder.  The largest block under ~2 MB (640 and 384 there) stays
# within 2% of the block sweep the walk replaced over a whole buffer
# (377 and 109 us, PR 26).  For the chunk and paged kernels the
# value comes from another toolchain's sweeps and is not measured on
# this chip.
KV_BLOCK_BUDGET = 2 * 1024 * 1024 + 128 * 1024

# rows whose queries and outputs ride one grid step of that kernel
ROWS_PER_STEP = 64


def auto_block_kv(l_buf: int, h_kv: int, dh: int) -> int:
    """Largest lane-multiple divisor of ``l_buf`` whose K+V blocks fit
    :data:`KV_BLOCK_BUDGET` (fallback: one lane)."""
    return max(
        (bl for bl in range(LANES, l_buf + 1, LANES)
         if l_buf % bl == 0 and 2 * h_kv * bl * dh <= KV_BLOCK_BUDGET),
        default=LANES,
    )


def fetch_ladder(granule: int) -> Tuple[int, ...]:
    """The widths, in tokens, one trip of the single-token walk may
    move: at most five lane multiples spaced geometrically from one
    lane block to the granule (640 -> 128, 256, 384, 640; 2176 -> 128,
    256, 512, 1024, 2176).  A copy's size is static, so a trip takes
    the smallest rung that covers the lane blocks its window touches,
    and each rung is one compiled body of the kernel: the spacing
    bounds what a trip moves beyond its live blocks to the ratio of
    two rungs, the count bounds the kernel's code."""
    blocks = granule // LANES
    rungs = min(5, 1 + (blocks - 1).bit_length())
    if rungs == 1:
        return (granule,)
    return tuple(sorted({
        LANES * round(blocks ** (i / (rungs - 1))) for i in range(rungs)
    }))


def trip_fetch(lo, hi, g, granule: int, xp=jnp):
    """``(first column, width)`` of what the walk's trip over granule
    ``g`` moves for the window ``[lo, hi)``, which touches that granule:
    the smallest rung of :func:`fetch_ladder` that covers the lane
    blocks of the granule the window touches, starting at the first of
    them, or earlier where the rung would pass the granule's end.  A
    granule the window covers is moved whole.  Pure arithmetic over
    ``xp`` (traced scalars in the kernel, numpy arrays on the host):
    the kernel's copies, its flash update and
    :func:`kv_tokens_fetched` all read it, so they cannot disagree."""
    ladder = fetch_ladder(granule)
    first = xp.maximum(lo, g * granule) // LANES * LANES
    end = (xp.minimum(hi, (g + 1) * granule) + LANES - 1) // LANES * LANES
    width = ladder[0] + sum(
        (end - first > a) * (b - a) for a, b in zip(ladder, ladder[1:])
    )
    return xp.minimum(first, (g + 1) * granule - width), width


def kv_tokens_fetched(lo, hi, l_buf: int, granule: int):
    """Tokens of K and V the single-token walk moves from HBM for each
    window ``[lo, hi)`` (arrays, one entry a row) of an ``l_buf``-slot
    buffer walked in granules of ``granule``: the sum of
    :func:`trip_fetch`'s widths over the granules a window touches; 0
    for an empty window.  The engine's ``kv_tokens_fetched`` counter."""
    lo = np.maximum(np.asarray(lo, np.int64), 0)
    hi = np.minimum(np.asarray(hi, np.int64), l_buf)
    total = np.zeros(lo.shape, np.int64)
    for g in range(l_buf // granule):
        touched = (hi > lo) & (lo < (g + 1) * granule) & (hi > g * granule)
        total += np.where(touched, trip_fetch(lo, hi, g, granule, np)[1], 0)
    return total


def pick_buffer_len(s: int, h_kv: int, dh: int) -> int:
    """Cache-buffer length for ``s`` live slots: the smallest lane
    multiple >= s whose :func:`auto_block_kv` block is fat (>= 384, or
    the whole buffer for short caches).

    The cache allocator must pick lengths the kernel can tile well: a
    buffer of 2176 slots (= 128 x 17) has no divisor between 128 and
    itself, so the BlockSpec-swept kernels degrade to 17 thin grid
    steps per row (half again as slow on another toolchain; not
    measured on this chip).  Up to a few extra
    padding blocks (beyond the decode cursor: masked AND clamp-skipped,
    so they cost bytes only at rest) buy a fat-block length."""
    base = -(-s // LANES) * LANES
    for cand in range(base, base + 4 * LANES + 1, LANES):
        if auto_block_kv(cand, h_kv, dh) >= min(384, cand):
            return cand
    return -(-base // 512) * 512


def quantize_kv(x: jax.Array, eps: float = 1e-8) -> Tuple[jax.Array, jax.Array]:
    """Per-row absmax int8: x (..., dh) -> (int8 values, f32 scales (...))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, eps) / 127.0
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


def _flash_block_update(
    q, k, ks, v, vs, mask_fn, scale, acc_ref, m_ref, l_ref,
):
    """ONE online-softmax block update — the arithmetic core every
    kernel in this family (dense single-token, dense multi-query, and
    the PAGED single-token one) shares.  Factoring it is what makes the
    paged kernel bit-identical to the dense one BY CONSTRUCTION: same ops,
    same shapes, same accumulation order — only where the K/V block's
    bytes came from differs (BlockSpec copy vs table-driven page DMA).

    ``mask_fn(shape)`` returns the valid-column mask for the (Hkv,
    rows, BLK) logit block; masked columns go to NEG_INF before the
    running max, so garbage bytes in skipped/out-of-window positions
    (uncopied pages in the paged kernels, not-yet-written slots in the
    dense ones) never reach the softmax."""
    # one batched dot over all KV heads: few fat grid steps beat
    # many thin ones (per-step overhead dominated the first cut)
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * scale                                   # (Hkv, rows, BLK)
    # K dequant on the logits; scales may be stored bf16 (round 5:
    # halves the scale-cache write stream) — cast in VMEM
    s = s * ks.astype(jnp.float32)
    s = jnp.where(mask_fn(s.shape), s, NEG_INF)

    m_prev = m_ref[:, :, :1]
    l_prev = l_ref[:, :, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # fully-masked-so-far rows keep exact zeros (exp(NEG_INF - NEG_INF)
    # would be 1): same guard as the bounded flash path
    p = jnp.where(m_new > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[:] = jnp.broadcast_to(
        alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape
    )
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    pv = (p * vs.astype(jnp.float32)).astype(q.dtype)
    # ^ V dequant on the probs (bf16 scale cast like K's)
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        pv, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )


def _flash_finalize(o_ref, acc_ref, l_ref, row=0):
    l = l_ref[:, :, :1]
    o_ref[row] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)).astype(
        o_ref.dtype
    )


# int8 rows of one packed sublane tile: the unit the append writes back
APPEND_TILE = 32


def _kernel(
    start_ref, stop_ref,  # scalar prefetch: (B,) int32 each
    q_ref, k_hbm, ks_hbm, v_hbm, vs_hbm,
    *refs,
    scale: float, granule: int, append: bool,
):
    """One grid step a BLOCK OF ROWS, whose queries and outputs sit in
    VMEM; K, V and their scales stay in HBM.  Each row walks the
    granules its window covers, one trip a granule, and a trip moves
    and attends only the lane blocks of its granule that the window
    touches (:func:`trip_fetch`: a granule inside the window whole, the
    window's first and last granule trimmed to a rung of
    :func:`fetch_ladder`).  Granule i+1's copies fly while granule i's
    flash update runs, and a row's last trip starts the first fetch of
    the next row that has one, so a one-granule row does not expose its
    fetch either.  A row whose window is empty starts no copy, computes
    nothing and costs one trip of a scalar loop.

    A fetch lands at the front of its slot and the flash update runs
    over the fetch's width and no column more: the slot's other columns
    hold whatever an earlier trip left (a stale scale can be NaN, and
    0 x NaN would pass the mask), so they never reach the arithmetic.

    With ``append`` the row's new token (``hi - 1``, the window's last
    column, always in the last trip's fetch) is not in the cache yet:
    the last trip patches it into the landed columns in VMEM, attends
    them, and copies the aligned tile that holds the column back to HBM
    (:data:`APPEND_TILE` int8 rows a head for K and V, one lane group
    of the scales) while the flash update runs.  The cache operands are
    the outputs' aliases, so that is the whole write."""
    if append:
        (kn_ref, ksn_ref, vn_ref, vsn_ref,
         o_ref, k_out, ks_out, v_out, vs_out,
         k_buf, ks_buf, v_buf, vs_buf, sem, slot_ref,
         acc_ref, m_ref, l_ref, wsem) = refs
    else:
        (o_ref, k_buf, ks_buf, v_buf, vs_buf, sem, slot_ref,
         acc_ref, m_ref, l_ref) = refs
    nb, _, l_buf, _ = k_hbm.shape
    rows = q_ref.shape[0]
    row0 = pl.program_id(0) * rows
    ladder = fetch_ladder(granule)

    def span(r):
        """Row r's clamped window and the granules it covers:
        (lo, hi, first granule, how many)."""
        lo = jnp.maximum(start_ref[r], 0)
        hi = jnp.minimum(stop_ref[r], l_buf)
        g0 = lo // granule
        n = jnp.where(hi > lo, (hi + granule - 1) // granule - g0, 0)
        return lo, hi, g0, n

    def next_row(r):
        """The first row >= r whose window is not empty (nb: none)."""
        return jax.lax.while_loop(
            lambda i: (i < nb) & (span(jnp.minimum(i, nb - 1))[3] == 0),
            lambda i: i + 1, r,
        )

    def fetch(lo, hi, g):
        """:func:`trip_fetch`; a granule inside the window (every one
        of a long window but its first and last) skips the arithmetic."""
        return jax.lax.cond(
            (lo <= g * granule) & (hi >= (g + 1) * granule),
            lambda: (g * granule, jnp.int32(granule)),
            lambda: trip_fetch(lo, hi, g, granule),
        )

    def at_rung(width, body):
        """``body(w)`` for the one rung ``w`` the traced ``width`` is:
        a copy's size and a block's shape are static, so each rung is
        its own code.  The granule itself is asked for first: a whole
        trip pays one comparison."""
        def ask(rungs):
            if len(rungs) == 1:
                return body(rungs[0])
            jax.lax.cond(width == rungs[0],
                         lambda: body(rungs[0]), lambda: ask(rungs[1:]))

        ask(ladder[::-1])

    def copies(r, col, w, slot):
        # the start and the wait halves build the SAME descriptors
        # (trip_fetch is a pure function of the row's window and the
        # granule), so each slot's semaphore always balances
        cols = pl.ds(pl.multiple_of(col, LANES), w)
        return [
            pltpu.make_async_copy(src, dst, sem.at[slot])
            for src, dst in (
                (k_hbm.at[r, :, cols, :], k_buf.at[slot, :, pl.ds(0, w), :]),
                (v_hbm.at[r, :, cols, :], v_buf.at[slot, :, pl.ds(0, w), :]),
                (ks_hbm.at[r, :, cols], ks_buf.at[slot, :, pl.ds(0, w)]),
                (vs_hbm.at[r, :, cols], vs_buf.at[slot, :, pl.ds(0, w)]),
            )
        ]

    def start(r, lo, hi, g, slot):
        col, width = fetch(lo, hi, g)

        def go(w):
            for cp in copies(r, col, w, slot):
                cp.start()

        at_rung(width, go)

    def start_first(r, slot):
        @pl.when(r < nb)
        def _start():
            lo, hi, g0, _ = span(r)
            start(r, lo, hi, g0, slot)

    def tile_of(c):
        return pl.multiple_of(c // APPEND_TILE * APPEND_TILE, APPEND_TILE)

    def patch(j, c, w, slot):
        """The new token into column ``c`` of the ``w`` landed columns.
        K and V: a select over the one int8 tile that holds the row, in
        int32 (a one-row int8 store would split a packed sublane); the
        scales: a select over the slot's (Hkv, w) columns."""
        rows32 = pl.ds(tile_of(c), APPEND_TILE)
        for new_ref, buf in ((kn_ref, k_buf), (vn_ref, v_buf)):
            tile = buf[slot, :, rows32, :].astype(jnp.int32)
            hit = tile_of(c) + jax.lax.broadcasted_iota(
                jnp.int32, tile.shape, 1
            ) == c
            buf[slot, :, rows32, :] = jnp.where(
                hit, new_ref[j][:, None, :], tile
            ).astype(buf.dtype)
        for new_ref, buf in ((ksn_ref, ks_buf), (vsn_ref, vs_buf)):
            block = buf[slot, :, pl.ds(0, w)].astype(jnp.float32)
            hit = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1) == c
            buf[slot, :, pl.ds(0, w)] = jnp.where(
                hit, new_ref[j][:, :1], block
            ).astype(buf.dtype)

    def write_backs(r, col, c, slot):
        """Copies VMEM -> HBM of the tile and the lane group that hold
        column ``c`` of the fetch that starts at buffer column ``col``;
        built alike to start and to wait for them, on one semaphore."""
        t0 = tile_of(c)
        l0 = pl.multiple_of(c // LANES * LANES, LANES)
        to_t0 = pl.multiple_of(col + t0, APPEND_TILE)
        to_l0 = pl.multiple_of(col + l0, LANES)
        return [
            pltpu.make_async_copy(src, dst, wsem.at[0])
            for src, dst in (
                (k_buf.at[slot, :, pl.ds(t0, APPEND_TILE), :],
                 k_out.at[r, :, pl.ds(to_t0, APPEND_TILE), :]),
                (v_buf.at[slot, :, pl.ds(t0, APPEND_TILE), :],
                 v_out.at[r, :, pl.ds(to_t0, APPEND_TILE), :]),
                (ks_buf.at[slot, :, pl.ds(l0, LANES)],
                 ks_out.at[r, :, pl.ds(to_l0, LANES)]),
                (vs_buf.at[slot, :, pl.ds(l0, LANES)],
                 vs_out.at[r, :, pl.ds(to_l0, LANES)]),
            )
        ]

    @pl.when(row0 == 0)
    def _prologue():
        slot_ref[0] = 0
        start_first(next_row(0), 0)

    def row(j, carry):
        r = row0 + j
        lo, hi, g0, n = span(r)

        @pl.when(n == 0)
        def _empty():
            o_ref[j] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

        @pl.when(n > 0)
        def _walk():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
            slot0 = slot_ref[0]
            q = q_ref[j]                           # (Hkv, Gp, dh)

            def trip(i, carry):
                slot = jax.lax.rem(slot0 + i, 2)
                g = g0 + i
                last = i + 1 == n
                col, width = fetch(lo, hi, g)
                c = hi - 1 - col           # the new token's column

                @pl.when(i + 1 < n)
                def _next_granule():
                    start(r, lo, hi, g + 1, 1 - slot)

                @pl.when(last)
                def _next_row():
                    start_first(next_row(r + 1), 1 - slot)

                def attend(w):
                    for cp in copies(r, col, w, slot):
                        cp.wait()

                    if append:
                        @pl.when(last)
                        def _append():
                            patch(j, c, w, slot)
                            for cp in write_backs(r, col, c, slot):
                                cp.start()

                    def mask_fn(shape):
                        cols = col + jax.lax.broadcasted_iota(
                            jnp.int32, shape, 2
                        )
                        return (cols >= lo) & (cols < hi)

                    attend_cols = pl.ds(0, w)
                    _flash_block_update(
                        q, k_buf[slot, :, attend_cols, :].astype(q.dtype),
                        ks_buf[slot, :, attend_cols][:, None, :],
                        v_buf[slot, :, attend_cols, :].astype(q.dtype),
                        vs_buf[slot, :, attend_cols][:, None, :],
                        mask_fn, scale, acc_ref, m_ref, l_ref,
                    )

                    if append:
                        # the tile has left this slot before the next
                        # row's first trip starts a fetch into it
                        @pl.when(last)
                        def _written():
                            for cp in write_backs(r, col, c, slot):
                                cp.wait()

                at_rung(width, attend)
                return carry

            jax.lax.fori_loop(0, n, trip, 0)
            slot_ref[0] = jax.lax.rem(slot0 + n, 2)
            _flash_finalize(o_ref, acc_ref, l_ref, j)

        return carry

    jax.lax.fori_loop(0, rows, row, 0)


def decode_attention(
    q: jax.Array,
    k8: jax.Array,
    ks: jax.Array,
    v8: jax.Array,
    vs: jax.Array,
    kv_start: Optional[jax.Array] = None,
    kv_stop: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    block_kv: Optional[int] = None,
    interpret: Optional[bool] = None,
    append: Optional[Tuple[jax.Array, ...]] = None,
):
    """Single-token attention against an int8 KV cache.

    q: (B, H, dh) current-token queries; k8/v8: (B, Hkv, L, dh) int8;
    ks/vs: (B, Hkv, 1, L) float per-(slot, head) scales — f32 or bf16
    (the decode cache stores bf16 since round 5: halves the dominant
    scale-write stream; the kernel upcasts in VMEM).  The singleton
    keeps the scale block TPU-tileable at zero byte cost;
    kv_start/kv_stop: (B,) int32 valid-slot windows (default: the whole
    buffer).  Cost follows the windows: a row is fetched and computed
    in granules of ``block_kv`` tokens (default :func:`auto_block_kv`)
    from its window's first to its last, the first and the last
    trimmed to the lane blocks the window touches
    (:func:`fetch_ladder`), and a row whose window is
    empty (start >= stop) costs nothing and returns zeros.  L and dh
    must be lane multiples (the cache allocator rounds L up; dh pads).
    Returns (B, H, dh) in q.dtype.

    ``append`` = (kq, ks_new, vq, vs_new), the current token's
    :func:`quantize_kv` ((B, Hkv, dh) int8 and (B, Hkv) scales): the
    token is NOT in the cache yet, and the kernel writes it at each
    row's ``kv_stop - 1`` (clamped to the buffer, as a
    ``dynamic_update_slice`` clamps) before attending it.  The four
    cache operands are updated in place (``input_output_aliases``) and
    returned: ``(out, k8, ks, v8, vs)``.  A row with an empty window
    is not written.
    """
    b, h, dh = q.shape
    _, h_kv, l_buf, _ = k8.shape
    if ks.shape != (b, h_kv, 1, l_buf) or vs.shape != (b, h_kv, 1, l_buf):
        raise ValueError(
            f"scales must be (B, Hkv, 1, L) = {(b, h_kv, 1, l_buf)}; got "
            f"ks {ks.shape}, vs {vs.shape}"
        )
    if h % h_kv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {h_kv}")
    if l_buf % LANES or dh % LANES:
        raise NotImplementedError(
            f"cache length {l_buf} and head dim {dh} must be multiples of "
            f"{LANES} (allocator contract)"
        )
    if interpret is None:
        interpret = interpret_default()
    scale = scale if scale is not None else 1.0 / (dh**0.5)
    if block_kv is None:
        granule = auto_block_kv(l_buf, h_kv, dh)
    else:
        granule = next(
            (bl for bl in (block_kv, 512, 256, LANES)
             if bl <= block_kv and bl % LANES == 0 and l_buf % bl == 0),
            None,
        )
        if granule is None:
            raise ValueError(
                f"block_kv={block_kv}: need a lane-multiple granule "
                f"(>= {LANES}) dividing the cache length {l_buf}"
            )

    start = (
        jnp.zeros((b,), jnp.int32) if kv_start is None
        else kv_start.astype(jnp.int32)
    )
    stop = (
        jnp.full((b,), l_buf, jnp.int32) if kv_stop is None
        else jnp.broadcast_to(kv_stop, (b,)).astype(jnp.int32)
    )
    if append is not None:
        kq, ks_new = append[:2]
        if kq.shape != (b, h_kv, dh) or ks_new.shape != (b, h_kv):
            raise ValueError(
                f"append must be (B, Hkv, dh) = {(b, h_kv, dh)} values and "
                f"(B, Hkv) scales; got {kq.shape}, {ks_new.shape}"
            )
    out = _walk(
        q, k8, ks, v8, vs, start, stop, *(append or ()),
        scale=scale, granule=granule, interpret=interpret,
    )
    return out[0] if append is None else out


@functools.partial(jax.jit, static_argnames=("scale", "granule", "interpret"))
def _walk(q, k8, ks, v8, vs, start, stop, *new, scale, granule, interpret):
    """:func:`decode_attention`'s kernel call, a jitted function of its
    own.  A model calls it once a layer with the same shapes, and the
    kernel's body holds a flash update a rung: a jitted callee is
    traced once a process and lowered once a program, where the bare
    call was traced and lowered again for every layer of every program
    (24 calls: 4.8 s a program before the rungs, 14 s with them, here,
    for a described v5e; 0.6 s as one callee).  XLA inlines the call:
    the op, its name and its aliases are what they were."""
    b, h, dh = q.shape
    _, h_kv, l_buf, _ = k8.shape
    rep = h // h_kv
    gp = max(SUBLANES, -(-rep // SUBLANES) * SUBLANES)
    # (B, H, dh) -> (B, Hkv, Gp, dh): group axis = sublanes of one block
    qg = q.reshape(b, h_kv, rep, dh)
    if gp != rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - rep), (0, 0)))

    # rows a grid step: their queries and outputs are one VMEM block
    # (16 KB a row at Hkv 8), so a row costs no pipeline step of its own
    rows = max(d for d in range(1, min(b, ROWS_PER_STEP) + 1) if b % d == 0)
    row = pl.BlockSpec((rows, h_kv, gp, dh), lambda i, *_: (i, 0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    # the scales as (B, Hkv, L): a (Hkv, granule) slice is whole
    # tiles, where Mosaic pads a (.., 1, L) bf16 memref to two rows
    # and refuses the one-row slice; the 3-D view is also XLA's own
    # layout for the (B, Hkv, 1, L) cache, so the reshape is free
    operands = [qg, k8, ks.reshape(b, h_kv, l_buf), v8,
                vs.reshape(b, h_kv, l_buf)]
    in_specs = [row, hbm, hbm, hbm, hbm]
    out_specs = row
    out_shape = jax.ShapeDtypeStruct((b, h_kv, gp, dh), q.dtype)
    scratch = [
        # two granule slots: one computes while the other lands
        pltpu.VMEM((2, h_kv, granule, dh), k8.dtype),
        pltpu.VMEM((2, h_kv, granule), ks.dtype),
        pltpu.VMEM((2, h_kv, granule, dh), v8.dtype),
        pltpu.VMEM((2, h_kv, granule), vs.dtype),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SMEM((1,), jnp.int32),  # slot of the next first granule
        pltpu.VMEM((h_kv, gp, dh), jnp.float32),
        pltpu.VMEM((h_kv, gp, LANES), jnp.float32),
        pltpu.VMEM((h_kv, gp, LANES), jnp.float32),
    ]
    aliases = {}
    if new:
        kq, ks_new, vq, vs_new = new
        # the new token rides in whole 32-bit tiles: int32 values (a
        # head a sublane), and each scale, rounded to the cache's
        # dtype as a plain write would round it, across one lane group
        new_kv = pl.BlockSpec((rows, h_kv, dh), lambda i, *_: (i, 0, 0))
        new_sc = pl.BlockSpec((rows, h_kv, LANES), lambda i, *_: (i, 0, 0))

        def lanes(x, like):
            x = x.astype(like.dtype).astype(jnp.float32)
            return jnp.broadcast_to(x[..., None], (b, h_kv, LANES))

        operands += [kq.astype(jnp.int32), lanes(ks_new, ks),
                     vq.astype(jnp.int32), lanes(vs_new, vs)]
        in_specs += [new_kv, new_sc, new_kv, new_sc]
        out_specs = [row, hbm, hbm, hbm, hbm]
        out_shape = [out_shape] + [
            jax.ShapeDtypeStruct(x.shape, x.dtype) for x in operands[1:5]
        ]
        scratch.append(pltpu.SemaphoreType.DMA((1,)))   # the write-backs
        # operand 0 and 1 are the prefetched windows
        aliases = {3 + i: 1 + i for i in range(4)}
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, granule=granule, append=bool(new)
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b // rows,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        # the slot parity and the prefetched first granule carry from
        # one grid step to the next: the steps run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="decode_attention",
    )(start, stop, *operands)
    if not new:
        return (out[:, :, :rep].reshape(b, h, dh),)
    out, k8, ks3, v8, vs3 = out
    return (out[:, :, :rep].reshape(b, h, dh), k8, ks3.reshape(ks.shape),
            v8, vs3.reshape(vs.shape))


def _kernel_chunk(
    start_ref, stop0_ref,  # scalar prefetch: (B,) int32 each
    q_ref, k_ref, ks_ref, v_ref, vs_ref,
    o_ref,
    acc_ref, m_ref, l_ref,
    *, scale: float, block_kv: int, rep: int, s_q: int,
    window: Optional[int] = None,
):
    """Multi-query flash-decode: S query tokens per row in one pass over
    the int8 cache (the chunked-prefill shape).

    Query tokens ride the SUBLANE axis next to their GQA group —
    row r = j * rep + g is query j, group head g — so the cache block
    is read ONCE for all S queries (the whole point: a chunk of S
    tokens costs one cache sweep, not S).  Causality is per sublane
    row: query j's window is [start, stop0 + j) where stop0 is query
    0's exclusive stop (its own cache slot + 1).  With ``window`` the
    start is per sublane row too: query j sees its last ``window``
    keys, [max(start, stop0 + j - window), stop0 + j)."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    lo = start_ref[b]
    stop0 = stop0_ref[b]
    hi_max = stop0 + (s_q - 1)
    # the first key any query of the tile sees
    lo_min = lo if window is None else jnp.maximum(lo, stop0 - window)
    live = (j * block_kv < hi_max) & ((j + 1) * block_kv > lo_min)

    def mask_fn(shape):
        cols = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, shape, 2)
        # per-sublane-row causal stop: row r is query r // rep.  Pad
        # rows beyond s_q*rep CLAMP to the last query's window — they
        # compute (zero-vector queries) and their output is sliced
        # away by the caller; the clamp keeps their window inside the
        # live range so nothing depends on pad-row masking
        qrow = jnp.minimum(
            jax.lax.broadcasted_iota(jnp.int32, shape, 1) // rep,
            s_q - 1,
        )
        seen = (cols >= lo) & (cols < stop0 + qrow)
        if window is not None:
            seen = seen & (cols >= stop0 + qrow - window)
        return seen

    @pl.when(live)
    def _step():
        q = q_ref[0]                               # (Hkv, Sp, dh)
        _flash_block_update(
            q, k_ref[0].astype(q.dtype), ks_ref[0],
            v_ref[0].astype(q.dtype), vs_ref[0],
            mask_fn, scale, acc_ref, m_ref, l_ref,
        )

    @pl.when(j == nk - 1)
    def _finalize():
        _flash_finalize(o_ref, acc_ref, l_ref)


# sublane budget for ONE multi-query kernel call's (Hkv, Sp, dh) f32
# scratch triple — also the QUERY TILE for wider chunks: an S above it
# runs ceil(S / CHUNK_MAX_SQ) kernel calls, each sweeping the live
# window once with kv_stop0 offset by its tile's position (exact: the
# chunk's K/V are in the cache before any attention runs, and query
# j's stop is position-indexed).  Whether wide chunks take the tiled
# kernels at all is wide_chunk_mode() — the XLA dequant path remains
# the reference and the non-TPU default.
CHUNK_MAX_SQ = 32
# query tiles a wide chunk runs as one kernel call EACH, written out in
# the program; a chunk of more tiles runs them as ONE kernel call inside
# a loop over the tiles.  A call written out is a kernel the compiler
# builds again (~0.09 s each for a described v5e: a 2,048-token chunk
# is 64 of them a layer, 512 a program of eight layers); a loop trip
# costs a few microseconds of control a tile.  Eight is the 256-token
# chunk: the widest that ran before chunks of thousands did
CHUNK_UNROLLED_TILES = 8


def wide_chunk_mode() -> str:
    """``MLCOMP_TPU_WIDE_CHUNK``: how chunk attention WIDER than the
    multi-query kernel tile (S > CHUNK_MAX_SQ — admission prefill
    chunks) runs against an int8 KV cache.

    - ``pallas``: query-TILED flash-kernel sweeps — ceil(S/32) passes
      over the live window, dequant in VMEM, no full-buffer bf16
      materialization;
    - ``xla``: the dequantize-the-whole-buffer XLA path (the PR-5
      reference — bandwidth-amortized at prefill widths, but it
      round-trips a full bf16 copy of the cache through HBM per layer
      per chunk);
    - ``auto`` (default): ``pallas`` on a real TPU, ``xla`` elsewhere
      (interpret-mode tiles would multiply CPU test wall for no
      fidelity gain — CPU correctness is proved by the dedicated
      interpret-mode equality tests).

    The engine and bare ``generate`` read the same knob, so their
    chunk numerics always match (the engine-vs-generate equality
    contract); dense and paged engines route consistently too, so
    paged-vs-dense bit-equality holds on every setting."""
    mode = os.environ.get("MLCOMP_TPU_WIDE_CHUNK", "auto")
    if mode not in ("auto", "pallas", "xla"):
        raise ValueError(
            f"MLCOMP_TPU_WIDE_CHUNK must be auto/pallas/xla, got {mode!r}"
        )
    if mode == "auto":
        mode = "pallas" if on_tpu() else "xla"
    return mode


def chunk_uses_kernels(s_q: int, mesh: bool = False) -> bool:
    """Kernel-vs-XLA routing of the transformer's int8 chunk
    attention: chunks up to ``CHUNK_MAX_SQ`` always ride the kernels;
    wider chunks do when :func:`wide_chunk_mode` says so; mesh-sharded
    serving never does (the kernels are single-chip)."""
    if mesh:
        return False
    return s_q <= CHUNK_MAX_SQ or wide_chunk_mode() == "pallas"


def decode_attention_chunk(
    q: jax.Array,
    k8: jax.Array,
    ks: jax.Array,
    v8: jax.Array,
    vs: jax.Array,
    kv_start: Optional[jax.Array] = None,
    kv_stop0: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Multi-query attention against an int8 KV cache: S chunk tokens
    per row in ONE sweep of the cache.  ``window``: query j attends its
    last ``window`` keys only, [max(kv_start, kv_stop0 + j - window),
    kv_stop0 + j), and blocks below every query's window are neither
    fetched nor computed.

    q: (B, S, H, dh) chunk queries whose K/V are ALREADY written to the
    cache at slots [stop0-1+j for j in range(S)]... i.e. query j sits
    at cache slot ``kv_stop0 - 1 + j`` and attends [kv_start,
    kv_stop0 + j).  The chunked-prefill shape
    (transformer._decode_attention_quant routes here where
    ``chunk_uses_kernels``).  The single-token kernel is the
    S == 1 special case (kv_stop0 == its kv_stop).

    Layout and masking follow :func:`decode_attention`; the only new
    machinery is the per-sublane causal stop.  Returns (B, S, H, dh).
    """
    b, s_q, h, dh = q.shape
    _, h_kv, l_buf, _ = k8.shape
    if ks.shape != (b, h_kv, 1, l_buf) or vs.shape != (b, h_kv, 1, l_buf):
        raise ValueError(
            f"scales must be (B, Hkv, 1, L) = {(b, h_kv, 1, l_buf)}; got "
            f"ks {ks.shape}, vs {vs.shape}"
        )
    if h % h_kv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {h_kv}")
    if s_q > CHUNK_MAX_SQ:
        # QUERY-TILED wide chunk (admission prefill widths): ceil(S/32)
        # kernel sweeps, each over the same already-written cache with
        # its tile's position folded into kv_stop0 — exact because
        # query j's causal stop is position-indexed and the chunk's
        # K/V landed in the cache before any attention ran.  Replaces
        # the old NotImplementedError; whether wide chunks come here
        # at all is the caller's wide_chunk_mode() routing.
        stop0 = (
            jnp.full((b,), l_buf - s_q + 1, jnp.int32) if kv_stop0 is None
            else jnp.broadcast_to(kv_stop0, (b,)).astype(jnp.int32)
        )

        def tile(q_tile, o):
            return decode_attention_chunk(
                q_tile, k8, ks, v8, vs, kv_start=kv_start,
                kv_stop0=stop0 + o, scale=scale, interpret=interpret,
                window=window,
            )

        n_tiles, rest = divmod(s_q, CHUNK_MAX_SQ)
        if n_tiles <= CHUNK_UNROLLED_TILES:
            return jnp.concatenate([
                tile(q[:, o:o + CHUNK_MAX_SQ], o)
                for o in range(0, s_q, CHUNK_MAX_SQ)
            ], axis=1)
        whole = n_tiles * CHUNK_MAX_SQ
        tiles = q[:, :whole].reshape(b, n_tiles, CHUNK_MAX_SQ, h, dh)
        out = jax.lax.map(
            lambda xs: tile(*xs),
            (tiles.transpose(1, 0, 2, 3, 4),
             jnp.arange(n_tiles, dtype=jnp.int32) * CHUNK_MAX_SQ),
        ).transpose(1, 0, 2, 3, 4).reshape(b, whole, h, dh)
        if rest:
            out = jnp.concatenate([out, tile(q[:, whole:], whole)], axis=1)
        return out
    if l_buf % LANES or dh % LANES:
        raise NotImplementedError(
            f"cache length {l_buf} and head dim {dh} must be multiples of "
            f"{LANES} (allocator contract)"
        )
    if interpret is None:
        interpret = interpret_default()
    scale = scale if scale is not None else 1.0 / (dh**0.5)
    blk = auto_block_kv(l_buf, h_kv, dh)
    nk = l_buf // blk

    rep = h // h_kv
    rows = s_q * rep
    sp = max(SUBLANES, -(-rows // SUBLANES) * SUBLANES)
    # (B, S, H, dh) -> (B, Hkv, Sp, dh), sublane row r = query*rep + g:
    # transpose the group axis next to the query axis, then flatten
    qg = q.reshape(b, s_q, h_kv, rep, dh).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(b, h_kv, rows, dh)
    if sp != rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, sp - rows), (0, 0)))

    start = (
        jnp.zeros((b,), jnp.int32) if kv_start is None
        else kv_start.astype(jnp.int32)
    )
    stop0 = (
        jnp.full((b,), l_buf - s_q + 1, jnp.int32) if kv_stop0 is None
        else jnp.broadcast_to(kv_stop0, (b,)).astype(jnp.int32)
    )

    def _clamp(b_, j, start_ref, stop0_ref):
        lo = start_ref[b_]
        if window is not None:
            lo = jnp.maximum(lo, stop0_ref[b_] - window)
        lo_b = jnp.minimum(lo // blk, nk - 1)
        hi_b = jnp.maximum(
            (stop0_ref[b_] + (s_q - 1) - 1) // blk, lo_b
        )
        return jnp.clip(j, lo_b, hi_b)

    def kvj(b_, j, start_ref, stop0_ref):
        return (b_, 0, _clamp(b_, j, start_ref, stop0_ref), 0)

    def ksj(b_, j, start_ref, stop0_ref):
        return (b_, 0, 0, _clamp(b_, j, start_ref, stop0_ref))

    out = pl.pallas_call(
        functools.partial(
            _kernel_chunk, scale=scale, block_kv=blk, rep=rep, s_q=s_q,
            window=window,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nk),
            in_specs=[
                pl.BlockSpec((1, h_kv, sp, dh), lambda b_, j, *_: (b_, 0, 0, 0)),
                pl.BlockSpec((1, h_kv, blk, dh), kvj),
                pl.BlockSpec((1, h_kv, 1, blk), ksj),
                pl.BlockSpec((1, h_kv, blk, dh), kvj),
                pl.BlockSpec((1, h_kv, 1, blk), ksj),
            ],
            out_specs=pl.BlockSpec(
                (1, h_kv, sp, dh), lambda b_, j, *_: (b_, 0, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((h_kv, sp, dh), jnp.float32),
                pltpu.VMEM((h_kv, sp, LANES), jnp.float32),
                pltpu.VMEM((h_kv, sp, LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h_kv, sp, dh), q.dtype),
        interpret=interpret,
        name="decode_attention_chunk",
    )(start, stop0, qg, k8, ks, v8, vs)
    out = out[:, :, :rows].reshape(b, h_kv, s_q, rep, dh)
    return out.transpose(0, 2, 1, 3, 4).reshape(b, s_q, h, dh)


# ---------------------------------------------------------------- paged
#
# The PAGED twin of the single-token kernel (mlcomp_tpu/kvpool): K/V
# live in (num_pages, Hkv, T, dh) page arrays addressed through a
# per-slot page table, and the kernels read them THROUGH the table —
# the table rides the scalar prefetch, and each grid step DMAs its
# block's pages straight from the pool arrays in HBM into VMEM
# scratch (the block-index-from-prefetched-table idiom the kvpool
# gather kernel proved, fused into the attention consumer).  No dense
# (slots, l_buf, ...) view ever materializes: the dense round trip the
# PR-7 sandwich paid (~2x the live slots' KV bytes per dispatch as
# pure data movement) is gone, and the kernel moves only the pages the
# window actually covers.
#
# Bit-equality with the dense kernels is BY CONSTRUCTION: the grid and
# block partition are the DENSE kernel's (auto_block_kv over the leaf
# buffer — pages are assembled into the same fat blocks, so the online
# softmax visits columns in the same order), and the arithmetic is the
# shared _flash_block_update.  Eligibility is therefore geometric: the
# dense block size must be a whole number of pages
# (paged_block_kv(...) is not None); other geometries take the lax
# gather-then-dense-kernel reference, which is equally exact.
#
# NULL pages (unmapped: left-pad prefix, beyond-span tail, not-yet-
# lazily-allocated decode pages) are pl.when-skipped like out-of-range
# blocks: their DMA never issues, the scratch keeps stale bytes, and
# the column mask removes them before the softmax.  GRAVE pages
# (retired rows' write sink) are only ever inside a DEAD row's window,
# whose output nothing reads — same contract as the dense kernel over
# a retired row's stale buffer.


def paged_block_kv(l_buf: int, h_kv: int, dh: int,
                   page_tokens: int) -> Optional[int]:
    """The dense kernel's block size for this geometry IF it is a
    whole number of pages (the paged kernels' eligibility gate), else
    None — callers fall back to the lax gather + dense kernel."""
    blk = auto_block_kv(l_buf, h_kv, dh)
    if l_buf % page_tokens == 0 and blk % page_tokens == 0:
        return blk
    return None


def paged_fetch_mode() -> str:
    """``MLCOMP_TPU_PAGED_FETCH``: how the paged kernels move a
    block's pages from the HBM pool arrays into VMEM.

    - ``double``: rolling DOUBLE BUFFER across grid steps — block
      j+1's page DMAs are STARTED before block j's flash update runs,
      so the next block's HBM traffic overlaps the current block's
      arithmetic (two block-scratch slots, one DMA semaphore each;
      only the row's first live block's fetch is exposed);
    - ``rolled``: the PR-8 serial start-then-wait-per-page fetch — the
      bisect/reference arm (identical bytes, zero overlap);
    - ``auto`` (default): ``double`` on a real TPU, ``rolled`` under
      interpret mode — emulated semaphores overlap nothing, they just
      add interpreter work per block, so CPU runs keep the reference
      schedule (the bit-equality tests pin both modes explicitly).

    Both modes are bit-exact vs each other and vs the lax gather
    reference: they move the same pages into the same block layout and
    run the same ``_flash_block_update`` — only WHEN the copies fly
    differs.  Read at trace time (an env flip needs a re-trace, like
    MLCOMP_TPU_PAGED_ATTN)."""
    mode = os.environ.get("MLCOMP_TPU_PAGED_FETCH", "auto")
    if mode not in ("auto", "double", "rolled"):
        raise ValueError(
            f"MLCOMP_TPU_PAGED_FETCH must be auto/double/rolled, "
            f"got {mode!r}"
        )
    if mode == "auto":
        mode = "double" if on_tpu() else "rolled"
    return mode


def _fetch_block_pages(
    tbl_ref, b, j, lo, hi, sem,
    kq_hbm, ks_hbm, vq_hbm, vs_hbm,
    k_buf, ks_buf, v_buf, vs_buf,
    *, page_tokens: int, pages_per_block: int, null_page: int,
):
    """ROLLED fetch: DMA block ``j``'s pages from the HBM pool arrays
    into the VMEM block scratch, table-driven, start-then-wait per
    page — the PR-8 reference the double-buffered path A/Bs against.
    Pages wholly outside [lo, hi) — and NULL pages — are skipped: no
    copy issues, and the stale scratch bytes land on columns the mask
    removes before the softmax.

    A ``fori_loop`` (one traced body) rather than a Python unroll:
    pages_per_block can run into the dozens at small page sizes, and
    an unrolled body that size multiplies COMPILE time per kernel —
    measured ~25% on the engine's CPU-interpret test matrix — for no
    runtime difference in THIS mode (the copies are serial by
    construction; ``double`` is the overlapped mode)."""
    T = page_tokens

    def body(p, _):
        col = j * pages_per_block + p
        pid = tbl_ref[b, col]
        t0 = col * T
        use = (t0 < hi) & (t0 + T > lo) & (pid != null_page)

        @pl.when(use)
        def _copy():
            # K/V pages are dense-layout tiles (Hkv, T, dh): they drop
            # into the block's sublane slice with no transpose
            for src, dst in ((kq_hbm, k_buf), (vq_hbm, v_buf)):
                cp = pltpu.make_async_copy(
                    src.at[pid], dst.at[:, pl.ds(p * T, T), :], sem
                )
                cp.start()
                cp.wait()
            for src, dst in ((ks_hbm, ks_buf), (vs_hbm, vs_buf)):
                cp = pltpu.make_async_copy(
                    src.at[pid], dst.at[:, pl.ds(p * T, T)], sem
                )
                cp.start()
                cp.wait()

        @pl.when(~use)
        def _blank():
            # a skipped page's K/V garbage is masked before the softmax
            # (int8 bytes are always finite), but SCALE garbage can be
            # a NaN bit pattern — and 0 * NaN would poison the p@V
            # accumulator straight through the mask.  Zero the scale
            # slices so skipped columns contribute exactly the dense
            # kernel's nothing (p is exactly 0 there).
            ks_buf[:, pl.ds(p * T, T)] = jnp.zeros(
                (ks_buf.shape[0], T), ks_buf.dtype
            )
            vs_buf[:, pl.ds(p * T, T)] = jnp.zeros(
                (vs_buf.shape[0], T), vs_buf.dtype
            )

        return _

    jax.lax.fori_loop(0, pages_per_block, body, 0)


def _page_copies(pid, p, bufs, kq_hbm, ks_hbm, vq_hbm, vs_hbm,
                 *, page_tokens: int):
    """The four async-copy descriptors landing physical page ``pid``
    at block offset ``p`` in buffer set ``bufs`` = (k, ks, v, vs,
    sem).  One builder shared by the START (prefetch) and WAIT
    (consume) halves of the double buffer, so both sides describe the
    SAME copies on the same semaphore."""
    T = page_tokens
    k_buf, ks_buf, v_buf, vs_buf, sem = bufs
    return (
        pltpu.make_async_copy(
            kq_hbm.at[pid], k_buf.at[:, pl.ds(p * T, T), :], sem
        ),
        pltpu.make_async_copy(
            vq_hbm.at[pid], v_buf.at[:, pl.ds(p * T, T), :], sem
        ),
        pltpu.make_async_copy(
            ks_hbm.at[pid], ks_buf.at[:, pl.ds(p * T, T)], sem
        ),
        pltpu.make_async_copy(
            vs_hbm.at[pid], vs_buf.at[:, pl.ds(p * T, T)], sem
        ),
    )


def _start_block_pages(
    tbl_ref, b, jb, lo, hi, bufs,
    kq_hbm, ks_hbm, vq_hbm, vs_hbm,
    *, page_tokens: int, pages_per_block: int, null_page: int,
):
    """START block ``jb``'s live page DMAs into ``bufs`` — no waits:
    the prefetch half of the rolling double buffer.  The skip
    predicate (window overlap + non-NULL) is a pure function of the
    prefetched scalars, so the wait half recomputes it EXACTLY and the
    per-semaphore start/wait counts always balance."""
    T = page_tokens

    def body(p, _):
        col = jb * pages_per_block + p
        pid = tbl_ref[b, col]
        t0 = col * T
        use = (t0 < hi) & (t0 + T > lo) & (pid != null_page)

        @pl.when(use)
        def _start():
            for cp in _page_copies(
                pid, p, bufs, kq_hbm, ks_hbm, vq_hbm, vs_hbm,
                page_tokens=T,
            ):
                cp.start()

        return _

    jax.lax.fori_loop(0, pages_per_block, body, 0)


def _wait_block_pages(
    tbl_ref, b, jb, lo, hi, bufs,
    kq_hbm, ks_hbm, vq_hbm, vs_hbm,
    *, page_tokens: int, pages_per_block: int, null_page: int,
):
    """WAIT for the copies ``_start_block_pages`` issued for block
    ``jb`` (reconstructed descriptors decrement the same per-buffer
    semaphore), and zero the scale slices of skipped pages — the same
    NaN-poisoning guard as the rolled fetch (see ``_blank`` there)."""
    T = page_tokens
    k_buf, ks_buf, v_buf, vs_buf, sem = bufs

    def body(p, _):
        col = jb * pages_per_block + p
        pid = tbl_ref[b, col]
        t0 = col * T
        use = (t0 < hi) & (t0 + T > lo) & (pid != null_page)

        @pl.when(use)
        def _wait():
            for cp in _page_copies(
                pid, p, bufs, kq_hbm, ks_hbm, vq_hbm, vs_hbm,
                page_tokens=T,
            ):
                cp.wait()

        @pl.when(~use)
        def _blank():
            ks_buf[:, pl.ds(p * T, T)] = jnp.zeros(
                (ks_buf.shape[0], T), ks_buf.dtype
            )
            vs_buf[:, pl.ds(p * T, T)] = jnp.zeros(
                (vs_buf.shape[0], T), vs_buf.dtype
            )

        return _

    jax.lax.fori_loop(0, pages_per_block, body, 0)


def _db_fetch_step(
    tbl_ref, b, j, nk, lo, hi, live_fn, compute,
    bufs0, bufs1,
    kq_hbm, ks_hbm, vq_hbm, vs_hbm,
    *, page_tokens: int, pages_per_block: int, null_page: int,
):
    """One grid step of the rolling double buffer, shared by the
    single-token and multi-query paged kernels (they differ only in
    their window/mask shapes):

    - at the row's first step, prefetch block 0 into buffer 0;
    - START block j+1's pages into buffer (j+1)%2 BEFORE touching
      block j's data — those DMAs fly while this step's
      ``_flash_block_update`` runs (the overlap this PR adds);
    - WAIT block j's copies in buffer j%2, then ``compute`` on it.

    Buffer parity is resolved with static ``pl.when`` branches (two
    buffer SETS, not a dynamically-indexed scratch axis), so every
    semaphore and scratch access is static.  Starts are gated by the
    SAME live/use predicates as waits, so no copy is ever started
    without its wait (an unbalanced semaphore would poison the next
    block sharing the slot)."""
    kw = dict(page_tokens=page_tokens, pages_per_block=pages_per_block,
              null_page=null_page)
    hbm = (kq_hbm, ks_hbm, vq_hbm, vs_hbm)
    even = jax.lax.rem(j, 2) == 0

    @pl.when((j == 0) & live_fn(0))
    def _prefetch_first():
        _start_block_pages(tbl_ref, b, 0, lo, hi, bufs0, *hbm, **kw)

    nxt = (j + 1 < nk) & live_fn(j + 1)

    @pl.when(nxt & even)           # j even -> block j+1 lands in bufs1
    def _start_odd():
        _start_block_pages(tbl_ref, b, j + 1, lo, hi, bufs1, *hbm, **kw)

    @pl.when(nxt & ~even)
    def _start_even():
        _start_block_pages(tbl_ref, b, j + 1, lo, hi, bufs0, *hbm, **kw)

    cur = live_fn(j)

    @pl.when(cur & even)
    def _consume_even():
        _wait_block_pages(tbl_ref, b, j, lo, hi, bufs0, *hbm, **kw)
        compute(bufs0)

    @pl.when(cur & ~even)
    def _consume_odd():
        _wait_block_pages(tbl_ref, b, j, lo, hi, bufs1, *hbm, **kw)
        compute(bufs1)


def _paged_kernel(
    start_ref, stop_ref, tbl_ref,  # scalar prefetch
    q_ref, kq_hbm, ks_hbm, vq_hbm, vs_hbm,
    o_ref,
    *scratch,
    scale: float, block_kv: int, page_tokens: int,
    pages_per_block: int, null_page: int, fetch: str,
):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nk = pl.num_programs(1)
    acc_ref, m_ref, l_ref = scratch[-3:]

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    lo = start_ref[b]
    hi = stop_ref[b]

    def live_fn(jb):
        return (jb * block_kv < hi) & ((jb + 1) * block_kv > lo)

    def mask_fn(shape):
        cols = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, shape, 2)
        return (cols >= lo) & (cols < hi)

    def compute(bufs):
        k_buf, ks_buf, v_buf, vs_buf, _sem = bufs
        q = q_ref[0]                               # (Hkv, Gp, dh)
        _flash_block_update(
            q, k_buf[:].astype(q.dtype), ks_buf[:][:, None, :],
            v_buf[:].astype(q.dtype), vs_buf[:][:, None, :],
            mask_fn, scale, acc_ref, m_ref, l_ref,
        )

    if fetch == "double":
        bufs0, bufs1 = scratch[0:5], scratch[5:10]
        _db_fetch_step(
            tbl_ref, b, j, nk, lo, hi, live_fn, compute, bufs0, bufs1,
            kq_hbm, ks_hbm, vq_hbm, vs_hbm,
            page_tokens=page_tokens, pages_per_block=pages_per_block,
            null_page=null_page,
        )
    else:
        bufs = scratch[0:5]

        @pl.when(live_fn(j))
        def _step():
            _fetch_block_pages(
                tbl_ref, b, j, lo, hi, bufs[4],
                kq_hbm, ks_hbm, vq_hbm, vs_hbm,
                bufs[0], bufs[1], bufs[2], bufs[3],
                page_tokens=page_tokens,
                pages_per_block=pages_per_block, null_page=null_page,
            )
            compute(bufs)

    @pl.when(j == nk - 1)
    def _finalize():
        _flash_finalize(o_ref, acc_ref, l_ref)


def _paged_call(
    kernel, q, kq_pages, ks_pages, vq_pages, vs_pages, table,
    start, stop, interpret: bool, fetch: Optional[str] = None,
):
    """The paged kernel's pallas_call plumbing: grid
    (B, nk) over dense-sized blocks, table prefetched as the third
    scalar, page arrays pinned in HBM (ANY), block scratch + online
    state in VMEM.  ``fetch`` picks the page-DMA schedule (default:
    :func:`paged_fetch_mode`): ``double`` allocates TWO block-scratch
    sets (+ one DMA semaphore each) and rolls the prefetch one block
    ahead of compute; ``rolled`` keeps the single-buffered PR-8
    reference."""
    from mlcomp_tpu.kvpool.allocator import NULL_PAGE

    if fetch is None:
        fetch = paged_fetch_mode()
    b = q.shape[0]
    _, h_kv, T, dh = kq_pages.shape
    mp = table.shape[1]
    l_buf = mp * T
    blk = paged_block_kv(l_buf, h_kv, dh, T)
    if blk is None:
        raise NotImplementedError(
            f"paged attention needs the dense block size "
            f"({auto_block_kv(l_buf, h_kv, dh)}) to be a whole number "
            f"of {T}-token pages over the {l_buf}-slot buffer; this "
            "geometry takes the lax gather path"
        )
    nk = l_buf // blk
    sp = q.shape[2]
    # scale pages ride the kernel as (P, Hkv, T): Mosaic pads a bf16
    # (.., 1, T) memref to a (2, 128) tile and then refuses the 1-row
    # page slice ("must be aligned to tiling (2)"); with the singleton
    # squeezed, a page is a whole (Hkv, T) tile and the block scratch
    # takes it at a lane offset.  compute() restores the singleton.
    ks_pages = ks_pages.reshape(ks_pages.shape[0], h_kv, T)
    vs_pages = vs_pages.reshape(vs_pages.shape[0], h_kv, T)
    block_set = [
        pltpu.VMEM((h_kv, blk, dh), kq_pages.dtype),
        pltpu.VMEM((h_kv, blk), ks_pages.dtype),
        pltpu.VMEM((h_kv, blk, dh), vq_pages.dtype),
        pltpu.VMEM((h_kv, blk), vs_pages.dtype),
        pltpu.SemaphoreType.DMA,
    ]
    scratch = block_set * (2 if fetch == "double" else 1) + [
        pltpu.VMEM((h_kv, sp, dh), jnp.float32),
        pltpu.VMEM((h_kv, sp, LANES), jnp.float32),
        pltpu.VMEM((h_kv, sp, LANES), jnp.float32),
    ]
    return pl.pallas_call(
        functools.partial(
            kernel, block_kv=blk, page_tokens=T,
            pages_per_block=blk // T, null_page=NULL_PAGE, fetch=fetch,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, nk),
            in_specs=[
                pl.BlockSpec((1, h_kv, sp, dh),
                             lambda b_, j, *_: (b_, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (1, h_kv, sp, dh), lambda b_, j, *_: (b_, 0, 0, 0)
            ),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((b, h_kv, sp, dh), q.dtype),
        interpret=interpret,
        # decode_attention_paged_kernel in device traces
        name="decode_attention" + kernel.func.__name__,
    )(start, stop, table, q, kq_pages, ks_pages, vq_pages, vs_pages)


def _check_paged_operands(h, kq_pages, ks_pages, vq_pages,
                          vs_pages, table):
    p_, h_kv, T, dh = kq_pages.shape
    if vq_pages.shape != kq_pages.shape:
        raise ValueError(
            f"K/V page shapes differ: {kq_pages.shape} vs {vq_pages.shape}"
        )
    want = (p_, h_kv, 1, T)
    if ks_pages.shape != want or vs_pages.shape != want:
        raise ValueError(
            f"scale pages must be {want}; got ks {ks_pages.shape}, "
            f"vs {vs_pages.shape}"
        )
    if h % h_kv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {h_kv}")
    if dh % LANES:
        raise NotImplementedError(
            f"head dim {dh} must be a multiple of {LANES} "
            "(allocator contract)"
        )
    if table.ndim != 2:
        raise ValueError(f"table must be (B, MP); got {table.shape}")
    return h_kv, T, dh


def paged_decode_attention(
    q: jax.Array,
    kq_pages: jax.Array,
    ks_pages: jax.Array,
    vq_pages: jax.Array,
    vs_pages: jax.Array,
    table: jax.Array,
    kv_start: Optional[jax.Array] = None,
    kv_stop: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    fetch: Optional[str] = None,
) -> jax.Array:
    """:func:`decode_attention` reading the int8 KV cache THROUGH a
    page table: q (B, H, dh); kq/vq pages (P, Hkv, T, dh) int8; ks/vs
    pages (P, Hkv, 1, T); ``table`` (B, MP) int32 maps row b's logical
    page j to a physical page (MP * T must equal the leaf buffer
    length, lane-aligned like the dense kernel's).  Windows and output
    exactly as the dense kernel — bit-identical on the same cache
    bytes (shared block partition + shared arithmetic).  ``fetch``
    overrides :func:`paged_fetch_mode` (the rolled-vs-double A/B)."""
    b, h, dh_q = q.shape
    h_kv, T, dh = _check_paged_operands(
        h, kq_pages, ks_pages, vq_pages, vs_pages, table
    )
    if dh_q != dh:
        raise ValueError(f"q head dim {dh_q} != page head dim {dh}")
    if interpret is None:
        interpret = interpret_default()
    l_buf = table.shape[1] * T
    scale = scale if scale is not None else 1.0 / (dh**0.5)

    rep = h // h_kv
    gp = max(SUBLANES, -(-rep // SUBLANES) * SUBLANES)
    qg = q.reshape(b, h_kv, rep, dh)
    if gp != rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - rep), (0, 0)))

    start = (
        jnp.zeros((b,), jnp.int32) if kv_start is None
        else kv_start.astype(jnp.int32)
    )
    stop = (
        jnp.full((b,), l_buf, jnp.int32) if kv_stop is None
        else jnp.broadcast_to(kv_stop, (b,)).astype(jnp.int32)
    )
    out = _paged_call(
        functools.partial(_paged_kernel, scale=scale),
        qg, kq_pages, ks_pages, vq_pages, vs_pages,
        table.astype(jnp.int32), start, stop, interpret, fetch=fetch,
    )
    return out[:, :, :rep].reshape(b, h, dh)


def sharded_decode_attention(
    q: jax.Array,
    k8: jax.Array,
    ks: jax.Array,
    v8: jax.Array,
    vs: jax.Array,
    mesh,
    kv_start: Optional[jax.Array] = None,
    kv_stop: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    append: Optional[Tuple[jax.Array, ...]] = None,
):
    """:func:`decode_attention` under a device mesh: a shard_map island
    with heads over ``tp`` and batch over the data axes.

    Attention is independent per (row, kv-head) — GQA groups stay whole
    because ``tp`` must divide BOTH head counts (each device keeps its
    query heads next to their shared KV head), so no cross-device math
    happens at all: the wrapper only pins a layout that matches the
    tp-sharded q/k/v projections feeding it (serve --mesh --kv-quant).
    ``append`` (the new token, :func:`decode_attention`) shards like
    the caches it lands in, and the four caches come back with them.
    """
    import jax as _jax
    from jax.sharding import PartitionSpec as P

    b, h, dh = q.shape
    h_kv = k8.shape[1]
    tp = mesh.shape.get("tp", 1)
    if tp > 1 and (h % tp or h_kv % tp):
        raise ValueError(
            f"int8 KV decode under tp={tp}: tp must divide both heads "
            f"({h}) and kv heads ({h_kv}) so GQA groups stay device-local"
        )
    dbatch = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
    rows_ax = ("dp", "fsdp") if b % dbatch == 0 else None
    head_ax = "tp" if tp > 1 else None
    l_buf = k8.shape[2]
    start = (
        jnp.zeros((b,), jnp.int32) if kv_start is None
        else kv_start.astype(jnp.int32)
    )
    stop = (
        jnp.full((b,), l_buf, jnp.int32) if kv_stop is None
        else jnp.broadcast_to(kv_stop, (b,)).astype(jnp.int32)
    )
    kv_spec = P(rows_ax, head_ax, None, None)
    q_spec = P(rows_ax, head_ax, None)
    in_specs = (q_spec, kv_spec, kv_spec, kv_spec, kv_spec, P(rows_ax),
                P(rows_ax))
    if append is None:
        def call(q, k8, ks, v8, vs, start, stop):
            return decode_attention(q, k8, ks, v8, vs, start, stop, scale)

        out_specs = q_spec
        append = ()
    else:
        def call(q, k8, ks, v8, vs, start, stop, *new):
            return decode_attention(
                q, k8, ks, v8, vs, start, stop, scale, append=new
            )

        sc_spec = P(rows_ax, head_ax)
        in_specs += (q_spec, sc_spec, q_spec, sc_spec)
        out_specs = (q_spec, kv_spec, kv_spec, kv_spec, kv_spec)
    fn = _jax.shard_map(
        call, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    return fn(q, k8, ks, v8, vs, start, stop, *append)
