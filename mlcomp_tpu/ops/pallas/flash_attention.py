"""Pallas TPU flash attention: blocked online-softmax, O(S) memory.

The reference framework has no custom attention kernels (torch SDPA inside
Catalyst models); this is where the TPU build spends its kernel budget.
Design follows the canonical TPU flash recipe:

- layout (B, H, S, D) inside the kernel (transposed from the framework's
  (B, S, H, D) at the wrapper), head_dim zero-padded to a lane multiple
  (128) — zero pads change nothing: q/k pads contribute 0 to logits, v/dO
  pads only produce discarded output columns;
- grid (B, H, num_q_blocks, num_kv_blocks), KV innermost: TPU grids run
  sequentially, so VMEM scratch (acc, running max m, running sum l)
  carries across KV steps; init at j == 0, finalize at j == nk - 1.
  EXCEPT the causal-unbounded forward, which runs a TRIANGULAR grid
  (B, H, live_pairs): per-step overhead is a large share of kernel time,
  so the schedule of live (i, j) pairs rides in as scalar-prefetch
  arrays and dead pairs get no grid step at all (measured 12% faster
  causal forward at S=4096 than the pl.when-skip rectangular grid);
- fp32 accumulation; probabilities cast back to the input dtype (bf16)
  for the MXU matmuls;
- on the rectangular grids, causal blocks fully above the diagonal are
  skipped via ``pl.when``; diagonal blocks are masked with
  ``broadcasted_iota``;
- rectangular-grid dead blocks (above the causal diagonal, or fully
  outside a row's KV window) skip their HBM→VMEM copies too: the K/V
  index maps clamp the block index into the live range, so the pipeline
  sees an unchanged index and elides the copy;
- GQA: KV-head index maps as ``h // rep`` — shared KV heads are read,
  never replicated in HBM;
- backward = custom VJP with two kernels (dq over KV blocks; dk/dv over
  Q blocks with the GQA group folded into the sequential grid axis),
  recomputing p from the saved logsumexp instead of storing S×S weights.
  The causal-unbounded backward is ONE kernel on the dk/dv grid where a
  KV head's float32 dq fits VMEM beside dk and dv (DQ_RESIDENT_BUDGET):
  scores, p and dP are then computed once a block pair, not twice.

Ragged sequence lengths (S % 128 != 0) stay on the kernel path: the
wrapper zero-pads S up to a lane multiple and folds the padded keys into
the per-row KV window so they are never attended; padded query rows are
sliced off outside the custom VJP, so their cotangents are identically
zero and gradients are untouched.  Falls back (NotImplementedError →
dispatch in ops/attention.py catches) only for S < 128, where pad waste
and launch overhead beat any kernel win.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mlcomp_tpu.ops.pallas import interpret_default

NEG_INF = -1e30
LANES = 128


def _pick_block(s: int, preferred: int = 512) -> int:
    for b in (preferred, 512, 256, 128):
        if b <= preferred and s % b == 0:
            return b
    raise NotImplementedError(f"sequence length {s} not a multiple of 128")


def _kv_block_clamp(j, i, b, causal, block_q, block_kv, nk, bounds_refs):
    """Clamp KV block index ``j`` into the live range for (batch b, q
    block i) — used inside K/V BlockSpec index maps.

    The Pallas pipeline elides the HBM→VMEM copy when a block's index is
    unchanged from the previous grid step, so mapping every dead step to
    the nearest live block means causally-dead and out-of-window blocks
    cost no bandwidth (their compute is already skipped via ``pl.when``).
    Clamping below the window prefetches the first live block early —
    also free.  Empty windows clamp to an arbitrary resident block; the
    kernel never reads it."""
    if causal:
        j = jnp.minimum(j, (i * block_q + block_q - 1) // block_kv)
    if bounds_refs is not None:
        lo_ref, hi_ref = bounds_refs
        lo_b = jnp.minimum(lo_ref[b] // block_kv, nk - 1)
        hi_b = jnp.maximum((hi_ref[b] - 1) // block_kv, lo_b)
        j = jnp.clip(j, lo_b, hi_b)
    return j


def _dot(a, b, trans_b: bool = False):
    dims = (((1,), (1 if trans_b else 0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _causal_mask(s, i, j, block_q, block_kv):
    rows = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(rows >= cols, s, NEG_INF)


def _bounds_mask(s, j, block_kv, lo, hi):
    """Mask key columns outside this batch row's valid [lo, hi) window."""
    cols = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where((cols >= lo) & (cols < hi), s, NEG_INF)


def _block_live(causal, i, j, block_q, block_kv, lo, hi):
    """Static causal skip + dynamic skip of blocks fully outside [lo, hi)."""
    live = (not causal) or (j * block_kv <= i * block_q + block_q - 1)
    if lo is None:
        return live
    return jnp.logical_and(
        live, (j * block_kv < hi) & ((j + 1) * block_kv > lo)
    )


def _softmax_update(s, v_ref, acc_ref, m_ref, l_ref, guard_masked: bool):
    """One online-softmax accumulation step — the ONE definition both the
    rectangular and triangular forward kernels use.  ``guard_masked``:
    zero probabilities on fully-masked columns (needed whenever a row's
    live window can be empty, i.e. the bounded path)."""
    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_next = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - m_next)
    if guard_masked:
        # a row whose live key set is empty has m_next == NEG_INF, making
        # exp(s - m_next) = 1 on masked cols; it must contribute nothing
        p = jnp.where(s > NEG_INF / 2, p, 0.0)
    l_next = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[:] = acc_ref[:] * alpha + _dot(p.astype(v_ref.dtype), v_ref[0, 0])
    m_ref[:] = jnp.broadcast_to(m_next, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_next, l_ref.shape)


def _finalize_out(o_ref, lse_ref, acc_ref, m_ref, l_ref):
    """Normalize the accumulator into the output block and store the lse
    (broadcast over a 128-lane minor dim: TPU lowering requires the last
    two block dims tileable to (8, 128), which a (1, 1, block_q) spec
    can't satisfy — same layout as the official TPU flash kernel)."""
    l = l_ref[:, :1]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
    lse_ref[0, 0] = jnp.broadcast_to(
        m_ref[:, :1] + jnp.log(l_safe), lse_ref[0, 0].shape
    ).astype(jnp.float32)


def _kernel_name(kernel) -> str:
    """Stable device-trace name of a (partial-wrapped) kernel body:
    the HLO custom call carries it, so profiles and ``GET /profile``
    show ``flash_fwd_kernel`` etc. instead of the enclosing jit's name."""
    return "flash" + getattr(kernel, "func", kernel).__name__


def _maybe_bounded_call(
    kernel, grid, in_specs, out_specs, out_shape, scratch, interpret,
    bounds, operands,
):
    """pallas_call with KV-bound scalar prefetch when ``bounds`` is set.

    One switch for forward and both backward kernels: bounded paths use a
    PrefetchScalarGridSpec with the two (B,) bound arrays prepended; index
    maps take ``*_`` so the appended scalar refs are ignored either way.
    """
    if bounds is not None:
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=grid,
                in_specs=in_specs,
                out_specs=out_specs,
                scratch_shapes=scratch,
            ),
            out_shape=out_shape,
            interpret=interpret,
            name=_kernel_name(kernel),
        )(*bounds, *operands)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name=_kernel_name(kernel),
    )(*operands)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _window_block_counts(kv_lo, kv_hi, nk: int, block_kv: int):
    """Per-batch first live KV block and live-block count, clamped to at
    least one block per row so every output block gets an (all-masked)
    finalize step — an empty window then produces exact zeros through the
    masked-probability guard, matching the rectangular path."""
    jlo = jnp.clip(kv_lo // block_kv, 0, nk - 1)
    jhi = jnp.clip((kv_hi - 1) // block_kv, 0, nk - 1)
    count = jnp.where(kv_hi > kv_lo, jnp.maximum(jhi - jlo + 1, 1), 1)
    return jlo.astype(jnp.int32), count.astype(jnp.int32)


def _bounded_schedule(
    kv_lo, kv_hi, b: int, nq: int, nk: int, block_kv: int,
    causal_block_q: Optional[int] = None,
):
    """DEVICE-built compressed schedule for the bounded fwd and dq
    passes: the (b, i, jj) enumeration keeps only jj < count steps,
    compacted to the front with a stable argsort, and the dynamic grid
    extent T = number of live steps — KV blocks outside a batch row's
    window get NO grid step at all (the bounded analog of
    _causal_schedule, which is static because causality is; windows are
    per-batch DATA, so this schedule is computed on device and rides in
    as scalar prefetch).  Segment boundaries (first/last flags) are
    per (b, i); compaction preserves segment contiguity because the sort
    is stable and dead steps only ever drop out of segment tails.

    ``causal_block_q`` set (to block_q) additionally intersects each
    (b, i) segment with the causal frontier — the ragged-causal case
    (left-padded decode prefill): count becomes per-(b, q block),
    clamped to >= 1 so an empty intersection still gets one all-masked
    finalize step (exact zeros via the guard, like empty windows)."""
    jlo, count = _window_block_counts(kv_lo, kv_hi, nk, block_kv)
    L = b * nq * nk
    e = jnp.arange(L, dtype=jnp.int32)
    eb = e // (nq * nk)
    ei = (e // nk) % nq
    ejj = e % nk
    if causal_block_q is not None:
        # causally-live kv blocks for q block i (cols <= last row)
        cb = ((jnp.arange(nq, dtype=jnp.int32) + 1) * causal_block_q - 1
              ) // block_kv + 1
        cnt = jnp.maximum(
            jnp.minimum(jlo[:, None] + count[:, None], cb[None, :])
            - jlo[:, None],
            1,
        )  # (b, nq)
        cnt_e = cnt[eb, ei]
    else:
        cnt_e = count[eb]
    live = ejj < cnt_e
    order = jnp.argsort(jnp.logical_not(live))  # stable: live first, in order
    eb, ejj, cnt_e = eb[order], ejj[order], cnt_e[order]
    bm = eb
    im = ei[order]
    jm = jnp.minimum(jlo[eb] + ejj, nk - 1)
    fst = (ejj == 0).astype(jnp.int32)
    lst = (ejj == cnt_e - 1).astype(jnp.int32)
    t_live = live.sum().astype(jnp.int32)
    return bm, im, jm, fst, lst, t_live


def _bounded_dkv_schedule(
    kv_lo, kv_hi, b: int, nq: int, nk: int, rep: int, block_kv: int,
    causal_block_q: Optional[int] = None,
):
    """Compressed (b, jj, g, i) schedule for the bounded dk/dv pass: one
    segment per live (b, kv block) accumulating over all (group, q block)
    pairs.  Dead KV blocks get no steps — their dk/dv output stays
    unwritten garbage, which the wrapper masks to zero (out-of-window
    keys have zero gradient by definition).

    With ``causal_block_q``, q blocks strictly above a KV block's causal
    diagonal are dropped from each segment too (the _dkv_schedule
    triangle, intersected per-batch with the window): the inner
    enumeration shrinks from rep*nq to rep*(nq - imin(j)) and remaps
    g-major over the surviving i range."""
    jlo, count = _window_block_counts(kv_lo, kv_hi, nk, block_kv)
    inner = rep * nq
    L = b * nk * inner
    e = jnp.arange(L, dtype=jnp.int32)
    eb = e // (nk * inner)
    r = e % (nk * inner)
    ejj = r // inner
    gi = r % inner
    jm_e = jnp.minimum(jlo[eb] + ejj, nk - 1)
    if causal_block_q is not None:
        imin = jnp.minimum((jm_e * block_kv) // causal_block_q, nq - 1)
        nqi = nq - imin
        live = (ejj < count[eb]) & (gi < rep * nqi)
    else:
        imin = jnp.zeros_like(gi)
        nqi = jnp.full_like(gi, nq)
        live = ejj < count[eb]
    order = jnp.argsort(jnp.logical_not(live))
    eb, ejj, gi = eb[order], ejj[order], gi[order]
    imin, nqi, jm = imin[order], nqi[order], jm_e[order]
    bm = eb
    gm = gi // nqi
    im = imin + gi % nqi
    fst = (gi == 0).astype(jnp.int32)
    lst = (gi == rep * nqi - 1).astype(jnp.int32)
    t_live = live.sum().astype(jnp.int32)
    return bm, jm, gm, im, fst, lst, t_live


def _fwd_kernel_bsched(
    lo_ref, hi_ref, bm_ref, im_ref, jm_ref, fst_ref, lst_ref,
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, scale, block_q, block_kv, causal=False,
):
    """Bounded forward on the compressed dynamic grid (axis 1 =
    live-step index; batch comes from the schedule); ``causal`` adds
    the diagonal mask for the ragged-causal case."""
    t = pl.program_id(1)
    b = bm_ref[t]
    j = jm_ref[t]

    @pl.when(fst_ref[t] == 1)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    s = _dot(q_ref[0, 0], k_ref[0, 0], trans_b=True) * scale
    s = _bounds_mask(s, j, block_kv, lo_ref[b], hi_ref[b])
    if causal:
        s = _causal_mask(s, im_ref[t], j, block_q, block_kv)
    _softmax_update(s, v_ref, acc_ref, m_ref, l_ref, guard_masked=True)

    @pl.when(lst_ref[t] == 1)
    def _finalize():
        _finalize_out(o_ref, lse_ref, acc_ref, m_ref, l_ref)


def _sched_enabled_for(causal: bool) -> bool:
    """ONE gate for all three dispatch sites (fwd, bwd, block pick) —
    they must agree or block tuning and grid scheme drift apart."""
    return (
        _bounded_sched_causal_enabled() if causal
        else _bounded_sched_enabled()
    )


def _flash_fwd_bsched(q, k, v, kv_lo, kv_hi, scale, block_q, block_kv,
                      interpret, causal=False):
    """Bounded forward via the device-built compressed schedule
    (padded-BERT windows; ``causal`` = ragged-causal prefill)."""
    b, h, s_q, d = q.shape
    h_kv, s_k = k.shape[1], k.shape[2]
    rep = h // h_kv
    nq, nk = s_q // block_q, s_k // block_kv
    bm, im, jm, fst, lst, t_live = _bounded_schedule(
        kv_lo, kv_hi, b, nq, nk, block_kv,
        causal_block_q=block_q if causal else None,
    )

    def qi(h_, t, lo, hi, bm, im, jm, f, l):
        return (bm[t], h_, im[t], 0)

    def kvj(h_, t, lo, hi, bm, im, jm, f, l):
        return (bm[t], h_ // rep, jm[t], 0)

    kernel = functools.partial(
        _fwd_kernel_bsched, scale=scale, block_q=block_q,
        block_kv=block_kv, causal=causal,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(h, t_live),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d), qi),
                pl.BlockSpec((1, 1, block_kv, d), kvj),
                pl.BlockSpec((1, 1, block_kv, d), kvj),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, d), qi),
                pl.BlockSpec((1, 1, block_q, LANES), qi),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s_q, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, s_q, LANES), jnp.float32),
        ],
        interpret=interpret,
        name=_kernel_name(kernel),
    )(kv_lo, kv_hi, bm, im, jm, fst, lst, q, k, v)
    return out, lse


def _bounded_sched_enabled() -> bool:
    """The compressed bounded path is default-on; the rectangular path
    stays selectable (MLCOMP_FLASH_BOUNDED_SCHED=0) for A/B measurement
    and as an escape hatch."""
    import os

    return os.environ.get("MLCOMP_FLASH_BOUNDED_SCHED", "1") not in (
        "0", "false",
    )


def _bounded_sched_causal_enabled() -> bool:
    """CAUSAL + windows (ragged left-padded prefill) defaults to the
    rectangular grid, opposite to the non-causal default: the causal
    clamp already skips most dead copies at large blocks, so on the
    representative serve mix (bucket sized to its longest prompt —
    windows 64..2048 at S=2048, B=8, H=16, v5e, marginal fori_loop
    timing) rectangular measured 1.37 ms fwd vs 2.07 scheduled.  The
    schedule wins 5.3x (0.22 vs 1.19 ms) when EVERY window is small
    (prompts <= S/8 in an oversized bucket) — workloads shaped like
    that should set MLCOMP_FLASH_BOUNDED_SCHED_CAUSAL=1.  The choice
    must be static: window values are runtime data.  Both paths are
    bit-identical (test_ragged_causal_scheduled_matches_rectangular)."""
    import os

    return os.environ.get(
        "MLCOMP_FLASH_BOUNDED_SCHED_CAUSAL", "0"
    ) not in ("0", "false") and _bounded_sched_enabled()


def _causal_schedule(nq: int, nk: int, block_q: int, block_kv: int):
    """Linearized live (i, j) causal pairs, i-major, plus first/last flags.

    The rectangular (i, j) grid spends a step on every pair even when the
    copy and compute are skipped — and per-step overhead is a large share
    of this kernel's time (measured: causal on the rectangular grid runs
    only ~8% faster than full attention despite half the compute).  A
    triangular grid iterates ONLY live pairs; the schedule rides in as
    scalar-prefetch arrays that both the index maps and the init/finalize
    predicates read (measured: 12% faster causal forward at S=4096)."""
    i_map, j_map, first, last = [], [], [], []
    for i in range(nq):
        j_hi = min(nk - 1, (i * block_q + block_q - 1) // block_kv)
        for j in range(j_hi + 1):
            i_map.append(i)
            j_map.append(j)
            first.append(1 if j == 0 else 0)
            last.append(1 if j == j_hi else 0)
    return (
        np.asarray(i_map, np.int32), np.asarray(j_map, np.int32),
        np.asarray(first, np.int32), np.asarray(last, np.int32),
    )


def _fwd_kernel_tri(
    im_ref, jm_ref, fst_ref, lst_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
    acc_ref, m_ref, l_ref, *, scale, block_q, block_kv,
):
    """Causal forward on the triangular grid (axis 2 = live-pair index)."""
    t = pl.program_id(2)
    i = im_ref[t]
    j = jm_ref[t]

    @pl.when(fst_ref[t] == 1)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    s = _dot(q_ref[0, 0], k_ref[0, 0], trans_b=True) * scale
    s = _causal_mask(s, i, j, block_q, block_kv)
    # causal ⇒ Sq == Sk ⇒ every row has a live key: no masked-prob guard
    _softmax_update(s, v_ref, acc_ref, m_ref, l_ref, guard_masked=False)

    @pl.when(lst_ref[t] == 1)
    def _finalize():
        _finalize_out(o_ref, lse_ref, acc_ref, m_ref, l_ref)


def _flash_fwd_tri(q, k, v, scale, block_q, block_kv, interpret):
    """Causal-unbounded forward via the triangular schedule."""
    b, h, s_q, d = q.shape
    h_kv, s_k = k.shape[1], k.shape[2]
    rep = h // h_kv
    nq, nk = s_q // block_q, s_k // block_kv
    im, jm, fst, lst = _causal_schedule(nq, nk, block_q, block_kv)

    def qi(b_, h_, t, im, jm, f, l):
        return (b_, h_, im[t], 0)

    def kvj(b_, h_, t, im, jm, f, l):
        return (b_, h_ // rep, jm[t], 0)

    kernel = functools.partial(
        _fwd_kernel_tri, scale=scale, block_q=block_q, block_kv=block_kv
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, h, len(im)),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d), qi),
                pl.BlockSpec((1, 1, block_kv, d), kvj),
                pl.BlockSpec((1, 1, block_kv, d), kvj),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, d), qi),
                pl.BlockSpec((1, 1, block_q, LANES), qi),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s_q, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, s_q, LANES), jnp.float32),
        ],
        interpret=interpret,
        name=_kernel_name(kernel),
    )(jnp.asarray(im), jnp.asarray(jm), jnp.asarray(fst), jnp.asarray(lst),
      q, k, v)
    return out, lse


def _fwd_kernel(
    *refs, scale, causal, block_q, block_kv, bounded
):
    if bounded:
        lo_ref, hi_ref, q_ref, k_ref, v_ref, o_ref, lse_ref = refs[:7]
        acc_ref, m_ref, l_ref = refs[7:]
        lo, hi = lo_ref[pl.program_id(0)], hi_ref[pl.program_id(0)]
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
        lo = hi = None
    i, j = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # skip KV blocks above the causal diagonal or outside the KV bounds
    live = _block_live(causal, i, j, block_q, block_kv, lo, hi)

    @pl.when(live)
    def _body():
        s = _dot(q_ref[0, 0], k_ref[0, 0], trans_b=True) * scale
        if causal:
            s = _causal_mask(s, i, j, block_q, block_kv)
        if bounded:
            s = _bounds_mask(s, j, block_kv, lo, hi)
        # bounded rows can have an EMPTY causal∩bounds window: guard the
        # masked probabilities so such rows contribute nothing
        _softmax_update(s, v_ref, acc_ref, m_ref, l_ref, guard_masked=bounded)

    @pl.when(j == nk - 1)
    def _finalize():
        _finalize_out(o_ref, lse_ref, acc_ref, m_ref, l_ref)


def _flash_fwd(q, k, v, kv_lo, kv_hi, scale, causal, block_q, block_kv, interpret):
    """q: (B, H, Sq, Dp); k/v: (B, Hkv, Sk, Dp); kv_lo/kv_hi: (B,) int32
    valid-key bounds or None.  Returns (out, lse)."""
    b, h, s_q, d = q.shape
    h_kv, s_k = k.shape[1], k.shape[2]
    rep = h // h_kv
    nq, nk = s_q // block_q, s_k // block_kv
    bounded = kv_lo is not None
    if causal and not bounded:
        # triangular grid: only live (i, j) pairs get grid steps
        return _flash_fwd_tri(q, k, v, scale, block_q, block_kv, interpret)
    if bounded and nk > 1 and _sched_enabled_for(causal):
        # compressed dynamic grid: out-of-window KV blocks get no steps
        # (for causal+bounded — ragged prefill — the schedule is the
        # window∩causal intersection; opt-in, see
        # _bounded_sched_causal_enabled).  nk == 1 has nothing to
        # compress — the whole-sequence block is already one step and
        # the rectangular path measured faster (v5e, S=512: rect-512
        # fwd+bwd 1.70 ms vs scheduled-256 1.85)
        return _flash_fwd_bsched(
            q, k, v, kv_lo, kv_hi, scale, block_q, block_kv, interpret,
            causal=causal,
        )

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_kv=block_kv, bounded=bounded,
    )
    # *refs: PrefetchScalarGridSpec appends the scalar refs to index-map
    # args.  K/V indices clamp dead blocks to the live range so their
    # copies are elided (see _kv_block_clamp).
    def kv_idx(b_, h_, i, j, *refs):
        j = _kv_block_clamp(
            j, i, b_, causal, block_q, block_kv, nk, refs if bounded else None
        )
        return (b_, h_ // rep, j, 0)

    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j, *_: (b, h, i, 0)),
        pl.BlockSpec((1, 1, block_kv, d), kv_idx),
        pl.BlockSpec((1, 1, block_kv, d), kv_idx),
    ]
    out_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j, *_: (b, h, i, 0)),
        pl.BlockSpec((1, 1, block_q, LANES), lambda b, h, i, j, *_: (b, h, i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, h, s_q, d), q.dtype),
        jax.ShapeDtypeStruct((b, h, s_q, LANES), jnp.float32),
    ]
    scratch_shapes = [
        pltpu.VMEM((block_q, d), jnp.float32),
        pltpu.VMEM((block_q, LANES), jnp.float32),
        pltpu.VMEM((block_q, LANES), jnp.float32),
    ]
    out, lse = _maybe_bounded_call(
        kernel, (b, h, nq, nk), in_specs, out_specs, out_shape,
        scratch_shapes, interpret,
        (kv_lo, kv_hi) if bounded else None, (q, k, v),
    )
    return out, lse


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------


def _bwd_update(q_blk, k_blk, v_blk, do_blk, lse_row, delta_row, scale, s,
                guard_masked=False, dq_acc=None, dkv_acc=None):
    """One backward accumulation step — the ONE definition every dq,
    dk/dv and fused kernel uses.  ``s`` is the (masked) logits block;
    ``guard_masked`` zeroes probabilities on fully-masked columns (the
    bounded paths, as in _softmax_update).  p, dP and dS are computed
    once and feed whichever accumulators the kernel holds: ``dq_acc``
    (float32, the q block's rows) and ``dkv_acc`` = (dk_acc, dv_acc)."""
    p = jnp.exp(s - lse_row)
    if guard_masked:
        p = jnp.where(s > NEG_INF / 2, p, 0.0)
    if dkv_acc is not None:
        dk_acc, dv_acc = dkv_acc
        dv_acc[:] += _dot(p.astype(do_blk.dtype).T, do_blk)
    dp = _dot(do_blk, v_blk, trans_b=True)
    # q, k, v and dO arrive in one dtype: one cast serves both products
    ds = (p * (dp - delta_row) * scale).astype(q_blk.dtype)
    if dkv_acc is not None:
        dk_acc[:] += _dot(ds.T, q_blk)
    if dq_acc is not None:
        dq_acc[:] += _dot(ds, k_blk)


def _dq_kernel(
    *refs, scale, causal, block_q, block_kv, bounded
):
    if bounded:
        lo_ref, hi_ref = refs[:2]
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc = refs[2:]
        lo, hi = lo_ref[pl.program_id(0)], hi_ref[pl.program_id(0)]
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc = refs
        lo = hi = None
    i, j = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    live = _block_live(causal, i, j, block_q, block_kv, lo, hi)

    @pl.when(live)
    def _body():
        s = _dot(q_ref[0, 0], k_ref[0, 0], trans_b=True) * scale
        if causal:
            s = _causal_mask(s, i, j, block_q, block_kv)
        if bounded:
            s = _bounds_mask(s, j, block_kv, lo, hi)
        # bounded: empty-window rows carry lse == NEG_INF and must not
        # contribute — the guard zeroes their masked probabilities
        _bwd_update(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0],
                    lse_ref[0, 0][:, :1], delta_ref[0, 0][:, :1], scale, s,
                    guard_masked=bounded, dq_acc=dq_acc)

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(
    *refs, scale, causal, block_q, block_kv, nq, bounded
):
    if bounded:
        lo_ref, hi_ref = refs[:2]
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
         dk_acc, dv_acc) = refs[2:]
        lo, hi = lo_ref[pl.program_id(0)], hi_ref[pl.program_id(0)]
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
         dk_acc, dv_acc) = refs
        lo = hi = None
    j, t = pl.program_id(2), pl.program_id(3)   # kv block, fused (rep, q block)
    i = t % nq                                  # q block within the group step
    nt = pl.num_programs(3)

    @pl.when(t == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    live = _block_live(causal, i, j, block_q, block_kv, lo, hi)

    @pl.when(live)
    def _body():
        s = _dot(q_ref[0, 0], k_ref[0, 0], trans_b=True) * scale
        if causal:
            s = _causal_mask(s, i, j, block_q, block_kv)
        if bounded:
            s = _bounds_mask(s, j, block_kv, lo, hi)
        _bwd_update(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0],
                    lse_ref[0, 0][:, :1], delta_ref[0, 0][:, :1], scale, s,
                    guard_masked=bounded, dkv_acc=(dk_acc, dv_acc))

    @pl.when(t == nt - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _dkv_schedule(nq: int, nk: int, rep: int, block_q: int, block_kv: int):
    """Live (j, g, i) triples for the causal dk/dv pass, j-major: q blocks
    strictly above a KV block's diagonal contribute nothing and get no
    grid step (the triangular counterpart of _causal_schedule)."""
    jm, gm, im, first, last = [], [], [], [], []
    for j in range(nk):
        i_lo = min(nq - 1, (j * block_kv) // block_q)
        for g in range(rep):
            for i in range(i_lo, nq):
                jm.append(j)
                gm.append(g)
                im.append(i)
                first.append(1 if (g == 0 and i == i_lo) else 0)
                last.append(1 if (g == rep - 1 and i == nq - 1) else 0)
    return (
        np.asarray(jm, np.int32), np.asarray(gm, np.int32),
        np.asarray(im, np.int32), np.asarray(first, np.int32),
        np.asarray(last, np.int32),
    )


def _dq_kernel_tri(
    im_ref, jm_ref, fst_ref, lst_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
    delta_ref, dq_ref, dq_acc, *, scale, block_q, block_kv,
):
    t = pl.program_id(2)
    i = im_ref[t]
    j = jm_ref[t]

    @pl.when(fst_ref[t] == 1)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    s = _dot(q_ref[0, 0], k_ref[0, 0], trans_b=True) * scale
    s = _causal_mask(s, i, j, block_q, block_kv)
    _bwd_update(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0],
                lse_ref[0, 0][:, :1], delta_ref[0, 0][:, :1], scale, s,
                dq_acc=dq_acc)

    @pl.when(lst_ref[t] == 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel_tri(
    jm_ref, gm_ref, im_ref, fst_ref, lst_ref, q_ref, k_ref, v_ref, do_ref,
    lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
    *, scale, block_q, block_kv,
):
    t = pl.program_id(2)
    i = im_ref[t]
    j = jm_ref[t]

    @pl.when(fst_ref[t] == 1)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    s = _dot(q_ref[0, 0], k_ref[0, 0], trans_b=True) * scale
    s = _causal_mask(s, i, j, block_q, block_kv)
    _bwd_update(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0],
                lse_ref[0, 0][:, :1], delta_ref[0, 0][:, :1], scale, s,
                dkv_acc=(dk_acc, dv_acc))

    @pl.when(lst_ref[t] == 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _dq_dkv_kernel_tri(
    jm_ref, gm_ref, im_ref, fst_ref, lst_ref, dqo_ref, q_ref, k_ref, v_ref,
    do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dq_hbm, dk_acc, dv_acc,
    dq_acc, dq_out, dq_sem, *, scale, block_q, block_kv, rep,
):
    """The whole causal backward on _dkv_schedule's grid: a step computes
    the scores, p, dP and dS once for all three gradients.  dk/dv
    accumulate over a KV block's (g, i) steps as in _dkv_kernel_tri; dq
    accumulates in ``dq_acc`` (rep, S_q, D) float32, resident over the
    whole sequential axis of one (b, h_kv).  A q block's rows are zeroed
    at KV block 0 (live for every q block) and leave at the last KV block
    under its diagonal (``dqo_ref[t]`` >= 0: the block's ordinal among
    those of its (b, h_kv)): per q block the sums arrive in ascending j,
    the order _dq_kernel_tri adds them in.  They leave cast, through ONE
    staging block and a copy to ``dq_hbm`` that the next leaving block
    (or the group's last step) waits for: a BlockSpec'd dq would hold
    rep x S_q x D twice more in VMEM, and the kernel has to fit the
    default scoped limit (see DQ_RESIDENT_BUDGET)."""
    t = pl.program_id(2)
    g = gm_ref[t]
    i = im_ref[t]
    j = jm_ref[t]
    leaving = dqo_ref[t]
    rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
    dq_copy = pltpu.make_async_copy(
        dq_out, dq_hbm.at[pl.program_id(0), pl.program_id(1) * rep + g, rows],
        dq_sem.at[0],
    )

    @pl.when(fst_ref[t] == 1)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(j == 0)
    def _init_dq():
        dq_acc[g, rows] = jnp.zeros(dq_out.shape, dq_acc.dtype)

    s = _dot(q_ref[0, 0], k_ref[0, 0], trans_b=True) * scale
    s = _causal_mask(s, i, j, block_q, block_kv)
    _bwd_update(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0],
                lse_ref[0, 0][:, :1], delta_ref[0, 0][:, :1], scale, s,
                dq_acc=dq_acc.at[g, rows], dkv_acc=(dk_acc, dv_acc))

    @pl.when(leaving >= 0)
    def _finalize_dq():
        @pl.when(leaving > 0)
        def _staging_free():
            dq_copy.wait()

        dq_out[:] = dq_acc[g, rows].astype(dq_out.dtype)
        dq_copy.start()

    @pl.when(lst_ref[t] == 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when(t == pl.num_programs(2) - 1)
    def _drain():
        dq_copy.wait()


def _dq_kernel_bsched(
    lo_ref, hi_ref, bm_ref, im_ref, jm_ref, fst_ref, lst_ref,
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
    *, scale, block_q, block_kv, causal=False,
):
    t = pl.program_id(1)
    b = bm_ref[t]
    j = jm_ref[t]

    @pl.when(fst_ref[t] == 1)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    s = _dot(q_ref[0, 0], k_ref[0, 0], trans_b=True) * scale
    s = _bounds_mask(s, j, block_kv, lo_ref[b], hi_ref[b])
    if causal:
        s = _causal_mask(s, im_ref[t], j, block_q, block_kv)
    _bwd_update(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0],
                lse_ref[0, 0][:, :1], delta_ref[0, 0][:, :1], scale, s,
                guard_masked=True, dq_acc=dq_acc)

    @pl.when(lst_ref[t] == 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel_bsched(
    lo_ref, hi_ref, bm_ref, jm_ref, gm_ref, im_ref, fst_ref, lst_ref,
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc, *, scale, block_q, block_kv, causal=False,
):
    t = pl.program_id(1)
    b = bm_ref[t]
    j = jm_ref[t]

    @pl.when(fst_ref[t] == 1)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    s = _dot(q_ref[0, 0], k_ref[0, 0], trans_b=True) * scale
    s = _bounds_mask(s, j, block_kv, lo_ref[b], hi_ref[b])
    if causal:
        s = _causal_mask(s, im_ref[t], j, block_q, block_kv)
    _bwd_update(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0],
                lse_ref[0, 0][:, :1], delta_ref[0, 0][:, :1], scale, s,
                guard_masked=True, dkv_acc=(dk_acc, dv_acc))

    @pl.when(lst_ref[t] == 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_bsched(scale, block_q, block_kv, interpret, q, k, v, kv_lo,
                      kv_hi, do, lse, delta, causal=False):
    """Bounded backward on compressed dynamic grids (the bounded analog
    of _flash_bwd_tri; schedules built on device from the windows,
    intersected with the causal triangle when ``causal``).  Unvisited
    dk/dv blocks (keys outside every window) are masked to zero at the
    wrapper — their gradient is zero by definition, and the kernel
    never wrote them."""
    b, h, s_q, d = q.shape
    h_kv, s_k = k.shape[1], k.shape[2]
    rep = h // h_kv
    nq, nk = s_q // block_q, s_k // block_kv

    bm, im, jm, fst, lst, t_live = _bounded_schedule(
        kv_lo, kv_hi, b, nq, nk, block_kv,
        causal_block_q=block_q if causal else None,
    )

    def qi(h_, t, lo, hi, bm, im, jm, f, l):
        return (bm[t], h_, im[t], 0)

    def kvj(h_, t, lo, hi, bm, im, jm, f, l):
        return (bm[t], h_ // rep, jm[t], 0)

    dq_kernel = functools.partial(
        _dq_kernel_bsched, scale=scale, block_q=block_q,
        block_kv=block_kv, causal=causal,
    )
    dq = pl.pallas_call(
        dq_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(h, t_live),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d), qi),
                pl.BlockSpec((1, 1, block_kv, d), kvj),
                pl.BlockSpec((1, 1, block_kv, d), kvj),
                pl.BlockSpec((1, 1, block_q, d), qi),
                pl.BlockSpec((1, 1, block_q, LANES), qi),
                pl.BlockSpec((1, 1, block_q, LANES), qi),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, d), qi),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name=_kernel_name(dq_kernel),
    )(kv_lo, kv_hi, bm, im, jm, fst, lst, q, k, v, do, lse, delta)

    bm2, jm2, gm2, im2, fst2, lst2, t2_live = _bounded_dkv_schedule(
        kv_lo, kv_hi, b, nq, nk, rep, block_kv,
        causal_block_q=block_q if causal else None,
    )

    def qh(hkv, t, lo, hi, bm, jm, gm, im, f, l):
        return (bm[t], hkv * rep + gm[t], im[t], 0)

    def kvh(hkv, t, lo, hi, bm, jm, gm, im, f, l):
        return (bm[t], hkv, jm[t], 0)

    dkv_kernel = functools.partial(
        _dkv_kernel_bsched, scale=scale, block_q=block_q,
        block_kv=block_kv, causal=causal,
    )
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=8,
            grid=(h_kv, t2_live),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d), qh),
                pl.BlockSpec((1, 1, block_kv, d), kvh),
                pl.BlockSpec((1, 1, block_kv, d), kvh),
                pl.BlockSpec((1, 1, block_q, d), qh),
                pl.BlockSpec((1, 1, block_q, LANES), qh),
                pl.BlockSpec((1, 1, block_q, LANES), qh),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_kv, d), kvh),
                pl.BlockSpec((1, 1, block_kv, d), kvh),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_kv, d), jnp.float32),
                pltpu.VMEM((block_kv, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
        name=_kernel_name(dkv_kernel),
    )(kv_lo, kv_hi, bm2, jm2, gm2, im2, fst2, lst2, q, k, v, do, lse, delta)

    # zero the gradients of keys no schedule segment visited: fully
    # out-of-window KV blocks hold uninitialized memory (in-window
    # blocks' masked columns already got exact zeros from the guard)
    cols = jnp.arange(s_k, dtype=jnp.int32)[None, None, :, None]
    in_window = (cols >= kv_lo[:, None, None, None]) & (
        cols < kv_hi[:, None, None, None]
    )
    dk = jnp.where(in_window, dk, 0).astype(k.dtype)
    dv = jnp.where(in_window, dv, 0).astype(v.dtype)

    z = lambda a: np.zeros(a.shape, jax.dtypes.float0)  # noqa: E731
    return dq, dk, dv, z(kv_lo), z(kv_hi)


def _flash_dq_tri(scale, block_q, block_kv, interpret, q, k, v, do, lse,
                  delta):
    """The causal-unbounded dq pass alone, on _causal_schedule's grid."""
    b, h, s_q, d = q.shape
    h_kv, s_k = k.shape[1], k.shape[2]
    rep = h // h_kv
    nq, nk = s_q // block_q, s_k // block_kv

    im, jm, fst, lst = _causal_schedule(nq, nk, block_q, block_kv)

    def qi(b_, h_, t, im, jm, f, l):
        return (b_, h_, im[t], 0)

    def kvj(b_, h_, t, im, jm, f, l):
        return (b_, h_ // rep, jm[t], 0)

    dq_kernel = functools.partial(
        _dq_kernel_tri, scale=scale, block_q=block_q, block_kv=block_kv
    )
    return pl.pallas_call(
        dq_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, h, len(im)),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d), qi),
                pl.BlockSpec((1, 1, block_kv, d), kvj),
                pl.BlockSpec((1, 1, block_kv, d), kvj),
                pl.BlockSpec((1, 1, block_q, d), qi),
                pl.BlockSpec((1, 1, block_q, LANES), qi),
                pl.BlockSpec((1, 1, block_q, LANES), qi),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, d), qi),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name=_kernel_name(dq_kernel),
    )(jnp.asarray(im), jnp.asarray(jm), jnp.asarray(fst), jnp.asarray(lst),
      q, k, v, do, lse, delta)


# The fused causal backward keeps one KV head's whole dq, float32, in
# VMEM: rep x S_q x D x 4 B.  4 MiB (S <= 4096 at a group of two heads of
# 128) is what fits the chip's DEFAULT scoped VMEM beside a step's blocks
# and products (15.75-16 MiB of 16 at 1024 / 1024 blocks, by the
# compiler's refusals); beyond it the backward runs the dq and the dk/dv
# kernel.  Not raised with ``vmem_limit_bytes``: a custom call that
# carries a limit makes XLA write a scoped-VMEM configuration on EVERY
# op of the program, and the train step's matmul fusions then lose what
# the kernel gains (PERF.md section 6, PR 45).
DQ_RESIDENT_BUDGET = 4 << 20


def _flash_bwd_tri(scale, block_q, block_kv, interpret, q, k, v, do, lse,
                   delta):
    """Causal-unbounded backward on triangular grids (see _causal_schedule
    — the same per-step-overhead argument as the forward).  Where a KV
    head's float32 dq fits DQ_RESIDENT_BUDGET, ONE kernel on the dk/dv
    grid gives dq too (_dq_dkv_kernel_tri: five S x S x D products a live
    block pair); else the dq pass and the dk/dv pass (seven), which give
    the same bits."""
    b, h, s_q, d = q.shape
    h_kv, s_k = k.shape[1], k.shape[2]
    rep = h // h_kv
    nq, nk = s_q // block_q, s_k // block_kv
    fused = rep * s_q * d * 4 <= DQ_RESIDENT_BUDGET

    jm, gm, im, fst, lst = _dkv_schedule(nq, nk, rep, block_q, block_kv)
    schedule = [jm, gm, im, fst, lst]

    def qh(b_, hkv, t, jm, gm, im, *_):
        return (b_, hkv * rep + gm[t], im[t], 0)

    def kvh(b_, hkv, t, jm, *_):
        return (b_, hkv, jm[t], 0)

    kernel = functools.partial(
        _dkv_kernel_tri, scale=scale, block_q=block_q, block_kv=block_kv
    )
    out_specs = [pl.BlockSpec((1, 1, block_kv, d), kvh)] * 2
    out_shape = [
        jax.ShapeDtypeStruct(k.shape, k.dtype),
        jax.ShapeDtypeStruct(v.shape, v.dtype),
    ]
    scratch = [pltpu.VMEM((block_kv, d), jnp.float32)] * 2
    if fused:
        kernel = functools.partial(
            _dq_dkv_kernel_tri, rep=rep, **kernel.keywords
        )
        # a q block leaves at the last KV block under its diagonal: its
        # ordinal among the q blocks of one (b, h_kv), -1 at other steps
        leaves = jm == np.minimum(
            nk - 1, (im * block_q + block_q - 1) // block_kv
        )
        schedule.append(
            np.where(leaves, np.cumsum(leaves) - 1, -1).astype(np.int32)
        )
        out_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        out_shape.append(jax.ShapeDtypeStruct(q.shape, q.dtype))
        scratch += [
            pltpu.VMEM((rep, s_q, d), jnp.float32),
            pltpu.VMEM((block_q, d), q.dtype),
            pltpu.SemaphoreType.DMA((1,)),
        ]
    else:
        dq = _flash_dq_tri(
            scale, block_q, block_kv, interpret, q, k, v, do, lse, delta
        )

    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(schedule),
            grid=(b, h_kv, len(jm)),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d), qh),
                pl.BlockSpec((1, 1, block_kv, d), kvh),
                pl.BlockSpec((1, 1, block_kv, d), kvh),
                pl.BlockSpec((1, 1, block_q, d), qh),
                pl.BlockSpec((1, 1, block_q, LANES), qh),
                pl.BlockSpec((1, 1, block_q, LANES), qh),
            ],
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        interpret=interpret,
        name=_kernel_name(kernel),
    )(*map(jnp.asarray, schedule), q, k, v, do, lse, delta)
    dk, dv = outs[:2]
    if fused:
        dq = outs[2]
    return dq, dk, dv, None, None


def _flash_bwd(scale, causal, block_q, block_kv, interpret, res, g,
               g_lse=None):
    q, k, v, kv_lo, kv_hi, out, lse = res
    b, h, s_q, d = q.shape
    h_kv, s_k = k.shape[1], k.shape[2]
    rep = h // h_kv
    nq, nk = s_q // block_q, s_k // block_kv
    do = g.astype(q.dtype)
    bounded = kv_lo is not None
    # the residual is one number a row, (B, H, S); the kernels read it
    # broadcast over a 128-lane minor dim (TPU block tiling)
    lse = jnp.broadcast_to(lse[..., None], (*lse.shape, LANES))

    # delta_i = sum_d dO_i * O_i — tiny elementwise reduce; XLA fuses it.
    # An lse cotangent folds in here exactly: dL/ds_ij has the out-path
    # term p_ij (dp_ij - delta_i) plus the lse-path term g_lse_i p_ij
    # (since dlse_i/ds_ij = p_ij), so shifting delta by -g_lse makes the
    # unchanged kernels compute the combined gradient.
    # Broadcast over a 128-lane minor dim like lse (TPU block tiling).
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, LANES))

    if causal and not bounded:
        # triangular grids: only live blocks get grid steps (mirrors the
        # forward; causal ⇒ no empty windows ⇒ no masked-prob guard)
        return _flash_bwd_tri(
            scale, block_q, block_kv, interpret, q, k, v, do, lse, delta
        )
    if bounded and nk > 1 and _sched_enabled_for(causal):
        # compressed dynamic grids (mirrors the forward's scheduled path
        # and gate — see _flash_fwd)
        return _flash_bwd_bsched(
            scale, block_q, block_kv, interpret, q, k, v, kv_lo, kv_hi,
            do, lse, delta, causal=causal,
        )

    def _call(kernel, grid, in_specs, out_specs, out_shape, scratch, operands):
        return _maybe_bounded_call(
            kernel, grid, in_specs, out_specs, out_shape, scratch,
            interpret, (kv_lo, kv_hi) if bounded else None, operands,
        )

    dq_kernel = functools.partial(
        _dq_kernel, scale=scale, causal=causal,
        block_q=block_q, block_kv=block_kv, bounded=bounded,
    )
    def kv_idx(b_, h_, i, j, *refs):
        j = _kv_block_clamp(
            j, i, b_, causal, block_q, block_kv, nk, refs if bounded else None
        )
        return (b_, h_ // rep, j, 0)

    dq = _call(
        dq_kernel,
        (b, h, nq, nk),
        [
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j, *_: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_kv, d), kv_idx),
            pl.BlockSpec((1, 1, block_kv, d), kv_idx),
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j, *_: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, LANES), lambda b, h, i, j, *_: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, LANES), lambda b, h, i, j, *_: (b, h, i, 0)),
        ],
        pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j, *_: (b, h, i, 0)),
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        [pltpu.VMEM((block_q, d), jnp.float32)],
        (q, k, v, do, lse, delta),
    )

    # dk/dv: one sequential pass per KV block over (group rep × q blocks),
    # so shared GQA KV heads accumulate all their query heads' contributions
    dkv_kernel = functools.partial(
        _dkv_kernel, scale=scale, causal=causal,
        block_q=block_q, block_kv=block_kv, nq=nq, bounded=bounded,
    )

    def qh(b, hkv, j, t, *_):
        i = t % nq
        if causal:
            # q blocks strictly above this KV block's diagonal are dead:
            # clamp to the first live one so their copies are elided
            i = jnp.maximum(i, (j * block_kv) // block_q)
        return (b, hkv * rep + t // nq, i, 0)

    dk, dv = _call(
        dkv_kernel,
        (b, h_kv, nk, rep * nq),
        [
            pl.BlockSpec((1, 1, block_q, d), qh),
            pl.BlockSpec((1, 1, block_kv, d), lambda b, hkv, j, t, *_: (b, hkv, j, 0)),
            pl.BlockSpec((1, 1, block_kv, d), lambda b, hkv, j, t, *_: (b, hkv, j, 0)),
            pl.BlockSpec((1, 1, block_q, d), qh),
            pl.BlockSpec((1, 1, block_q, LANES), qh),
            pl.BlockSpec((1, 1, block_q, LANES), qh),
        ],
        [
            pl.BlockSpec((1, 1, block_kv, d), lambda b, hkv, j, t, *_: (b, hkv, j, 0)),
            pl.BlockSpec((1, 1, block_kv, d), lambda b, hkv, j, t, *_: (b, hkv, j, 0)),
        ],
        [
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        [
            pltpu.VMEM((block_kv, d), jnp.float32),
            pltpu.VMEM((block_kv, d), jnp.float32),
        ],
        (q, k, v, do, lse, delta),
    )
    if not bounded:
        return dq, dk, dv, None, None

    z = lambda a: np.zeros(a.shape, jax.dtypes.float0)  # noqa: E731
    return dq, dk, dv, z(kv_lo), z(kv_hi)


# --------------------------------------------------------------------------
# public wrapper
# --------------------------------------------------------------------------


def _kernel_layout(q, k, v, d):
    """(B, S, H, D) → (B, H, S, D) with head_dim zero-padded to a lane
    multiple — the shared entry transform for both public wrappers."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    d_pad = (LANES - d % LANES) % LANES
    if d_pad:
        pad = [(0, 0), (0, 0), (0, 0), (0, d_pad)]
        qt, kt, vt = (jnp.pad(x, pad) for x in (qt, kt, vt))
    return (qt, kt, vt), d_pad


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, kv_lo, kv_hi, scale, causal, block_q, block_kv, interpret):
    out, _ = _flash_fwd(
        q, k, v, kv_lo, kv_hi, scale, causal, block_q, block_kv, interpret
    )
    return out


# The names a rematerialised layer keeps (models/transformer.py hands
# them to ``save_only_these_names``): the kernel's own residuals, so the
# backward pass neither calls the forward kernel a second time nor redoes
# the projections, RoPE and transposes in front of it.  Outside
# ``jax.checkpoint`` a name is an identity.
REMAT_SAVED_NAMES = ("flash_q", "flash_k", "flash_v", "flash_out", "flash_lse")


def _named_fwd(q, k, v, kv_lo, kv_hi, scale, causal, block_q, block_kv,
               interpret):
    """The forward kernel with its five residuals under
    ``REMAT_SAVED_NAMES``.  ``out`` is named ONCE and that value is both
    the primal output and the residual: a name on the residual alone
    leaves the recompute in need of ``out`` downstream (the o projection)
    and the second kernel call stays.  ``lse`` is kept as (B, H, S): a
    trailing axis of 1 would be padded back to 128 lanes by the chip's
    tiled layout."""
    q = checkpoint_name(q, "flash_q")
    k = checkpoint_name(k, "flash_k")
    v = checkpoint_name(v, "flash_v")
    out, lse = _flash_fwd(
        q, k, v, kv_lo, kv_hi, scale, causal, block_q, block_kv, interpret
    )
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse[..., 0], "flash_lse")
    return out, lse, (q, k, v, kv_lo, kv_hi, out, lse)


def _flash_vjp_fwd(q, k, v, kv_lo, kv_hi, scale, causal, block_q, block_kv, interpret):
    out, _, res = _named_fwd(
        q, k, v, kv_lo, kv_hi, scale, causal, block_q, block_kv, interpret
    )
    return out, res


_flash.defvjp(_flash_vjp_fwd, _flash_bwd)


# ---- (out, lse) variant: building block for ring attention -----------------
#
# Ring attention merges per-KV-shard partial results with the online-
# softmax rule, which needs each block's logsumexp alongside its
# (normalized) output.  The lse is genuinely differentiable here (the
# merge weights depend on it), handled by the delta shift in _flash_bwd.


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_pair(q, k, v, kv_lo, kv_hi, scale, causal, block_q, block_kv,
                interpret):
    out, lse = _flash_fwd(
        q, k, v, kv_lo, kv_hi, scale, causal, block_q, block_kv, interpret
    )
    return out, lse[..., 0]


def _flash_pair_vjp_fwd(q, k, v, kv_lo, kv_hi, scale, causal, block_q,
                        block_kv, interpret):
    out, lse, res = _named_fwd(
        q, k, v, kv_lo, kv_hi, scale, causal, block_q, block_kv, interpret
    )
    return (out, lse), res


def _flash_pair_bwd(scale, causal, block_q, block_kv, interpret, res, gs):
    g_out, g_lse = gs
    return _flash_bwd(
        scale, causal, block_q, block_kv, interpret, res, g_out, g_lse=g_lse
    )


_flash_pair.defvjp(_flash_pair_vjp_fwd, _flash_pair_bwd)


def flash_attention_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Like :func:`flash_attention` but also returns the per-row
    logsumexp (B, Sq, H) — the ring-attention building block.  Requires
    lane-tileable shapes (no pad shim: ring shards are uniform) and no KV
    windows.  NOTE: every row must have at least one live key (guaranteed
    here: causal requires Sq == Sk, so row i always attends key i) — this
    unbounded path has no masked-probability guard, so an empty-window
    row would get the uniform-average failure the bounded kernel guards
    against; ring "skip" blocks must use a sentinel instead of calling
    the kernel.  Differentiable in (q, k, v) including the lse output's
    cotangent path."""
    b, s_q, h, d = q.shape
    s_k, h_kv = k.shape[1], k.shape[2]
    if h % h_kv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {h_kv}")
    if s_q < LANES or s_k < LANES or s_q % LANES or s_k % LANES:
        raise NotImplementedError(f"untileable ring shard: {s_q}/{s_k}")
    if causal and s_q != s_k:
        raise NotImplementedError("causal flash needs Sq == Sk")
    block_q = block_q or _pick_block(s_q, preferred=1024 if causal else 512)
    block_kv = block_kv or _pick_block(s_k, preferred=1024)
    if interpret is None:
        interpret = interpret_default()
    scale = scale if scale is not None else 1.0 / (d**0.5)

    (qt, kt, vt), d_pad = _kernel_layout(q, k, v, d)
    out, lse = _flash_pair(
        qt, kt, vt, None, None, float(scale), bool(causal),
        block_q, block_kv, bool(interpret),
    )
    if d_pad:
        out = out[..., :d]
    # (B, H, Sq, D) -> (B, Sq, H, D); lse (B, H, Sq) -> (B, Sq, H)
    return jnp.swapaxes(out, 1, 2), jnp.swapaxes(lse, 1, 2)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: Optional[float] = None,
    kv_start: Optional[jax.Array] = None,
    kv_stop: Optional[jax.Array] = None,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash attention over framework-layout tensors.

    q: (B, Sq, H, D); k, v: (B, Sk, Hkv, D) with Hkv | H (GQA).
    ``kv_start``/``kv_stop``: optional (B,) int32 per-row valid-key
    windows — keys outside [start, stop) are masked (right-padded BERT
    batches: stop = lengths; left-padded prompts: start = pad counts).
    Non-causal windowed paths with more than one KV block run a
    COMPRESSED DYNAMIC GRID (r3): the schedule of live (b, i, j) steps
    is built on device from the windows and rides in as scalar prefetch,
    so out-of-window blocks get no grid step at all — measured on v5e,
    window 256/2048 (B8 H8 D128) runs fwd+bwd 26% faster than the
    rectangular grid whose pl.when/copy-skip only saved ~3% (grid-step
    overhead dominates).  Single-KV-block shapes (S=512 at default
    blocks) keep the rectangular grid: one whole-sequence step is
    already minimal and measured faster.  Causal+windowed (ragged causal
    pads) stays rectangular with compute/copy skip.  A query row whose
    causal∩window key set is empty outputs 0 (NOT the uniform average
    the XLA reference degrades to — such rows are padding by contract).
    Ragged lengths (S % 128 != 0, S >= 128) are zero-padded up to a lane
    multiple and the pad keys masked via the window machinery — the
    kernel path is kept, gradients are exact (pad/slice sits outside the
    custom VJP).  Returns (B, Sq, H, D). Differentiable (custom VJP).
    """
    b, s_q, h, d = q.shape
    s_k, h_kv = k.shape[1], k.shape[2]
    if h % h_kv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {h_kv}")
    if s_q < LANES or s_k < LANES:
        raise NotImplementedError(f"flash needs S >= {LANES}; got {s_q}/{s_k}")
    if causal and s_q != s_k:
        # the kernel's diagonal is position-aligned; offset-causal
        # (chunked prefill) goes through the masked XLA path instead
        raise NotImplementedError(f"causal flash needs Sq == Sk; got {s_q}/{s_k}")
    pad_sq = (LANES - s_q % LANES) % LANES
    pad_sk = (LANES - s_k % LANES) % LANES
    if interpret is None:
        interpret = interpret_default()
    scale = scale if scale is not None else 1.0 / (d**0.5)

    kv_lo = kv_hi = None
    if kv_start is not None or kv_stop is not None or pad_sk:
        # defaults use the ORIGINAL s_k: padded keys must never attend
        kv_lo = (
            jnp.zeros((b,), jnp.int32) if kv_start is None
            else kv_start.astype(jnp.int32)
        )
        kv_hi = (
            jnp.full((b,), s_k, jnp.int32) if kv_stop is None
            else kv_stop.astype(jnp.int32)
        )

    if pad_sq or pad_sk:
        # pad rows/keys up to a block multiple; padded q rows are junk
        # that the final slice discards (their cotangent is zero, so
        # backward is untouched); padded keys are outside every row's
        # [kv_lo, kv_hi) window so they never contribute
        q = jnp.pad(q, ((0, 0), (0, pad_sq), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad_sk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_sk), (0, 0), (0, 0)))
    s_qp, s_kp = s_q + pad_sq, s_k + pad_sk
    # measured on v5e at S=4096 (B4 H8 D128): fewer grid steps amortize
    # per-step overhead better than small blocks exploit skip granularity
    # — KV block 1024 beats 512 by ~25% fwd; under the causal TRIANGULAR
    # grids 1024/1024 is best overall (fwd+bwd 16.7 ms vs 18.4 at
    # 512/1024), while the rectangular (bounded/non-causal) backward
    # prefers q block 512.  Bounded NON-causal paths prefer KV block 512:
    # the compressed dynamic-grid schedule (r3) drops out-of-window
    # blocks entirely, and finer blocks drop more (v5e, S=2048 window
    # 256: scheduled-512 fwd+bwd 3.43 ms vs rectangular-512 4.64)
    # the 512 preference belongs to the SCHEDULED path only: with the
    # escape hatch off (MLCOMP_FLASH_BOUNDED_SCHED=0) the rectangular
    # kernels keep their round-2 tuning (1024), so A/B comparisons don't
    # conflate iteration scheme with block size
    bounded_sched = kv_lo is not None and _sched_enabled_for(causal)
    block_q = block_q or _pick_block(
        s_qp, preferred=1024 if causal else 512
    )
    block_kv = block_kv or _pick_block(
        s_kp, preferred=512 if bounded_sched else 1024
    )
    if s_qp % block_q or s_kp % block_kv:
        raise NotImplementedError("sequence lengths must tile into blocks")

    (qt, kt, vt), d_pad = _kernel_layout(q, k, v, d)

    out = _flash(qt, kt, vt, kv_lo, kv_hi, float(scale), bool(causal),
                 block_q, block_kv, bool(interpret))
    if d_pad:
        out = out[..., :d]
    if pad_sq:
        out = out[:, :, :s_q]
    return jnp.swapaxes(out, 1, 2)
