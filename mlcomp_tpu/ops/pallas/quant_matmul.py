"""Pallas TPU int8 weight-only matmul: dequantize in VMEM, not in HBM.

Decode is weight-bandwidth-bound: every generated token re-reads every
weight matrix once while activations are tiny (B rows).  Storing weights
int8 halves the HBM traffic — but only if the dequantize happens INSIDE
the kernel, after the int8 block is already in VMEM.  XLA cannot do this
with a jnp ``q.astype(bf16) * scale`` prefix: it materializes the
dequantized copy in HBM once per scan step (measured slower than plain
bf16 in round 1, models/generation.py).  This kernel is that missing
fusion:

    out[B, N] = (x[B, D] @ q8[D, N]) * scale[N]

- per-output-channel scales commute with the contraction, so the scale
  multiply happens once on the (B, N) accumulator, not on the (D, N)
  weights;
- q8 blocks upcast int8→bf16 in registers/VMEM; the MXU runs a normal
  bf16 matmul (x is bf16);
- grid (N blocks, D blocks), D innermost: fp32 accumulator scratch
  carries across D steps (same pattern as the flash kernel);
- B is padded to the 8-sublane minimum; decode batches are small, the
  padding rows are sliced off at the wrapper.

The same kernel serves stacked per-layer weights via vmap at the caller
(scales are per-(layer, channel) after ops/quant.py's stacked-axis fix).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mlcomp_tpu.ops.pallas import interpret_default

SUBLANES = 8
LANES = 128


def _kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, out_dtype):
    j = pl.program_id(2)
    nd = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x = x_ref[:]                                   # (Bp, BD) bf16
    q = q_ref[:].astype(x.dtype)                   # int8 -> bf16 in VMEM
    acc_ref[:] += jax.lax.dot_general(
        x, q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(j == nd - 1)
    def _finalize():
        # s_ref is the (8, BN) broadcast tile; row 0 carries the data
        o_ref[:] = (acc_ref[:] * s_ref[0:1]).astype(out_dtype)


def _kernel_norm(x_ref, g_ref, q_ref, s_ref, o_ref, y_ref, *,
                 out_dtype, norm_dtype, eps):
    """RMSNorm folded into the matmul prologue (decode glue attack,
    round 5): this variant REQUIRES the full contraction in one block
    (block_d == D — the decode-GEMV auto-block layout), so the
    row-wise norm is computed on the resident x block in VMEM and the
    whole contraction finishes in this one grid step: no D-loop, no
    accumulator scratch.  The standalone norm kernel, its HBM
    round-trip of the normed activations, and its launch disappear
    from the per-token step.  Math mirrors models/transformer.rmsnorm
    exactly: f32 square-mean + rsqrt, scale, cast to the norm module's
    dtype — then the usual bf16 MXU matmul.

    The normed rows land in a VMEM scratch computed once per ROW block
    (the n axis is the inner grid loop; the x block is grid-invariant
    along it) — recomputing the norm per output-column block measured
    as pure repeated VPU work on the widest shape (lm_head: 32 n-steps
    re-norming the same 8 rows)."""
    @pl.when(pl.program_id(1) == 0)
    def _norm_rows():
        x32 = x_ref[:].astype(jnp.float32)         # (Bp, D) full rows
        ms = jnp.mean(x32 * x32, axis=1, keepdims=True)
        y_ref[:] = (
            x32 * jax.lax.rsqrt(ms + eps) * g_ref[:].astype(jnp.float32)
        ).astype(norm_dtype).astype(jnp.bfloat16)

    q = q_ref[:].astype(jnp.bfloat16)
    acc = jax.lax.dot_general(
        y_ref[:], q, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[:] = (acc * s_ref[0:1]).astype(out_dtype)


_GEMV_ROWS = 64  # row count at or below which the decode heuristic kicks in


def _auto_blocks(b: int, d: int, n: int):
    """Block sizes for the (rows, contraction, out) problem shape.

    Decode GEMVs (rows <= _GEMV_ROWS) are per-GRID-STEP-overhead bound,
    not bandwidth bound: a (8, 2048)x(2048, 2048) call at the round-3
    512x512 default runs 16 grid steps of 256 KB and measures 9.2 us
    where the HBM roofline is 5.1 us.  The v5e sweeps (tools/exp_*,
    marginal fori_loop timing, in-process) converge on full-D blocks up
    to 2048 with ~1-2 MB per block and >= 4 grid steps: (2048, 512)
    blocks measure 93.7% of the bytes-roofline on the fused gate_up
    (2048x16384) vs 79.3% for 4 MB blocks, 84.0% on the down-proj
    (8192x2048, beating both wider-N and deeper-D variants), and the
    very-wide lm_head (2048x32768) prefers (2048, 1024) at 88.6%.
    Too-few fat steps lose the pipeline's fill/drain amortization;
    too-thin steps pay per-step overhead.  Larger row counts (prefill
    interception) keep the measured round-2 512x512 default — there the
    x/acc blocks share VMEM and bandwidth, and fat weight blocks would
    evict them.
    """
    if b > _GEMV_ROWS:
        return 512, 512
    block_d = min(d, 2048)
    block_n = 512 if n <= 16384 else 1024
    return min(block_n, n), block_d


def quant_matmul(
    x: jax.Array,
    q8: jax.Array,
    scale: jax.Array,
    block_n: int | None = None,
    block_d: int | None = None,
    interpret: bool | None = None,
    prebroadcast_scale: bool = False,
    norm_scale: jax.Array | None = None,
    norm_dtype=None,
    norm_eps: float = 1e-6,
) -> jax.Array:
    """``x @ (q8 * scale)`` with the dequant fused into the kernel.

    x: (B, D) float (bf16/f32); q8: (D, N) int8; scale: (D-broadcastable,
    N) or (N,) float — per-output-channel.  Returns (B, N) in x.dtype.
    ``block_n``/``block_d`` default to a shape-dependent heuristic (see
    :func:`_auto_blocks`); pass them to pin a layout.  Falls back
    (NotImplementedError) when D or N don't tile; the caller
    (ops/quant.py dispatch) keeps the XLA path for those.

    ``norm_scale`` ((D,) f32) additionally folds an RMSNorm of x into
    the kernel prologue (``y = rmsnorm(x) @ (q8 * scale)``): x arrives
    UN-normed in any float dtype, the norm runs in f32 on the resident
    row, casts through ``norm_dtype`` (the norm module's output dtype)
    to bf16, and the matmul proceeds as usual — the output is bf16
    (what the un-fused path's pre-cast input would have produced).
    Requires the full contraction in one block (block_d == D, the
    decode-GEMV layout); raises NotImplementedError otherwise so the
    caller can norm explicitly and retry.
    """
    b, d = x.shape
    d2, n = q8.shape
    if d != d2:
        raise ValueError(f"contraction mismatch: x {x.shape} vs q8 {q8.shape}")
    if block_n is None or block_d is None:
        auto_n, auto_d = _auto_blocks(b, d, n)
        block_n = auto_n if block_n is None else block_n
        block_d = auto_d if block_d is None else block_d
    # accept only per-output-channel layouts: (n,) or (1, n) — or, with
    # ``prebroadcast_scale=True`` (an explicit caller CONTRACT, not a
    # shape inference: the kernel reads row 0 only, so a genuinely
    # non-uniform (8, n) array would be silently wrong), the
    # (SUBLANES, n) tile ops/quant.fold_kernel_leaves prepares, keeping
    # the tile-shaped broadcast OUT of a decode loop's per-step work.
    # A scale that merely has n elements (e.g. a per-input-row (d, 1)
    # on a square kernel) would silently produce wrong outputs — the
    # kernel assumes scales commute with the contraction.
    prebroadcast = bool(prebroadcast_scale)
    if prebroadcast and scale.shape != (SUBLANES, n):
        raise ValueError(
            f"prebroadcast_scale needs shape ({SUBLANES}, {n}); got "
            f"{scale.shape}"
        )
    if not prebroadcast:
        if scale.shape == (1, n):
            scale = scale.reshape(n)
        if scale.shape != (n,):
            raise ValueError(
                f"scale must be per-output-channel, shape ({n},) or "
                f"(1, {n}); got {scale.shape}"
            )
    # largest preferred block that divides the dim — the SAME rule
    # kernel_consumable (ops/quant.py) checks against, so anything it
    # admits tiles here (any lane multiple works via the 128 fallback)
    block_d = _fit_block(d, block_d)
    block_n = _fit_block(n, block_n)
    if block_d is None or block_n is None:
        raise NotImplementedError(
            f"shapes must tile into lane multiples: D={d}, N={n}"
        )
    if norm_scale is not None:
        if block_d != d:
            raise NotImplementedError(
                f"norm folding needs the full contraction in one block "
                f"(block_d == D); got block_d={block_d}, D={d}"
            )
        if norm_scale.shape != (d,):
            raise ValueError(
                f"norm_scale must be ({d},); got {norm_scale.shape}"
            )
    if interpret is None:
        interpret = interpret_default()

    # tile the row axis too: interception covers the PREFILL pass, where
    # rows = B*S can be thousands — an untiled row axis would put a
    # rows x block_n fp32 accumulator in VMEM
    bp = max(SUBLANES, -(-b // SUBLANES) * SUBLANES)
    block_b = min(256, bp)
    bp = -(-bp // block_b) * block_b
    if bp != b:
        x = jnp.pad(x, ((0, bp - b), (0, 0)))
    # scale rides as an (8, N) broadcast so its block meets the TPU
    # (8, 128) min tile; row 0 is the real data
    if prebroadcast:
        s2 = scale.astype(jnp.float32)
    else:
        s2 = jnp.broadcast_to(
            scale.astype(jnp.float32)[None, :], (SUBLANES, n)
        )

    if norm_scale is not None:
        # fused-norm variant: x arrives un-normed (any float dtype);
        # output is bf16 — exactly what the un-fused path's pre-cast
        # normed input would have produced.  g rides as a (1, D) block
        # (a free reshape — materializing an (8, D) broadcast per call
        # measured ~0.6 us/call of pure in-loop glue)
        g2 = norm_scale.astype(jnp.float32).reshape(1, d)
        kernel = functools.partial(
            _kernel_norm, out_dtype=jnp.bfloat16,
            norm_dtype=norm_dtype or jnp.bfloat16, eps=norm_eps,
        )
        out = pl.pallas_call(
            kernel,
            grid=(bp // block_b, n // block_n),
            in_specs=[
                pl.BlockSpec((block_b, d), lambda r, i: (r, 0)),
                pl.BlockSpec((1, d), lambda r, i: (0, 0)),
                pl.BlockSpec((d, block_n), lambda r, i: (0, i)),
                pl.BlockSpec((SUBLANES, block_n), lambda r, i: (0, i)),
            ],
            out_specs=pl.BlockSpec((block_b, block_n), lambda r, i: (r, i)),
            out_shape=jax.ShapeDtypeStruct((bp, n), jnp.bfloat16),
            scratch_shapes=[pltpu.VMEM((block_b, d), jnp.bfloat16)],
            interpret=interpret,
            name="quant_matmul_norm",
        )(x, g2, q8, s2)
        return out[:b]

    kernel = functools.partial(_kernel, out_dtype=x.dtype)
    out = pl.pallas_call(
        kernel,
        grid=(bp // block_b, n // block_n, d // block_d),
        in_specs=[
            pl.BlockSpec((block_b, block_d), lambda r, i, j: (r, j)),
            pl.BlockSpec((block_d, block_n), lambda r, i, j: (j, i)),
            pl.BlockSpec((SUBLANES, block_n), lambda r, i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((block_b, block_n), lambda r, i, j: (r, i)),
        out_shape=jax.ShapeDtypeStruct((bp, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_b, block_n), jnp.float32)],
        interpret=interpret,
        name="quant_matmul",
    )(x, q8, s2)
    return out[:b]


def _fit_block(dim: int, preferred: int):
    """Largest lane-multiple block <= preferred that divides ``dim``."""
    for blk in range(min(preferred, dim) // LANES * LANES, 0, -LANES):
        if dim % blk == 0:
            return blk
    return None
