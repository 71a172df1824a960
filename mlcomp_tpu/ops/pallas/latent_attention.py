"""Latent attention's single-token step: one block for keys and values.

The cache keeps one latent a token for ALL heads: ``latent_dim``
numbers that every head's keys and values are projections of, and
``rope_dim`` more that every head's key shares as they are.  With the
up-projections absorbed into the query and into the output
(``models/latent_attention.py``) a head's score against a token is its
absorbed query times the token's whole cached row, and its value is the
row's first ``latent_dim`` numbers.  So a row's live blocks are fetched
ONCE each and serve as keys and as values for all the heads: the heads
are the rows of one matrix product a block.

The cache stays in HBM.  For every row that holds a window the kernel
walks the blocks its window ``[start, stop)`` covers, one trip a block:
block i+1's copy flies while block i's flash update runs, and a row's
last trip starts the first fetch of the next row that has one.  Blocks
outside a row's window are never fetched, and a row whose window is
empty starts no copy, computes nothing and is not written.

The row's new token (slot ``stop - 1``) is not in the cache yet: the
last trip patches it into the landed block in VMEM, attends it, and
copies the one aligned tile that holds it back to HBM while the flash
update runs.  The cache operand is the output's alias, so that is the
whole write (``ops/pallas/decode_attention.py`` appends the same way).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mlcomp_tpu.ops.pallas import interpret_default

LANES = 128
# tokens a fetch: 512 rows of 640 bfloat16 lanes are 655 KB
BLOCK = 512
# rows of the aligned tile the append writes back (a packed bfloat16
# sublane tile; two of float32's)
TILE = 16
ROWS_PER_STEP = 8
NEG_INF = -1e30


def block_of(length: int) -> int:
    """Tokens a block of a cache of ``length`` slots."""
    return min(BLOCK, -(-length // TILE) * TILE)


def buffer_len(length: int) -> int:
    """Slots a cache of ``length`` is allocated: whole blocks (the
    slots past ``length`` lie beyond every window)."""
    block = block_of(length)
    return -(-length // block) * block


def blocks_fetched(start, stop, block: int):
    """Blocks the walk fetches for windows ``[start, stop)``."""
    return jnp.where(
        stop > start, (stop + block - 1) // block - start // block, 0
    )


def _kernel(start_ref, stop_ref, q_ref, new_ref, lat_hbm, o_ref, lat_out,
            buf, sem, slot_ref, acc_ref, m_ref, l_ref, wsem, *,
            block: int, dc: int, precision):
    nb, l_buf, _ = lat_hbm.shape
    rows = q_ref.shape[0]
    row0 = pl.program_id(0) * rows

    def span(r):
        """Row r's clamped window and the blocks it covers:
        (lo, hi, first block, how many)."""
        lo = jnp.maximum(start_ref[r], 0)
        hi = jnp.minimum(stop_ref[r], l_buf)
        return lo, hi, lo // block, blocks_fetched(lo, hi, block)

    def next_row(r):
        """The first row >= r whose window is not empty (nb: none)."""
        return jax.lax.while_loop(
            lambda i: (i < nb) & (span(jnp.minimum(i, nb - 1))[3] == 0),
            lambda i: i + 1, r,
        )

    def copy(r, g, slot):
        cols = pl.ds(pl.multiple_of(g * block, block), block)
        return pltpu.make_async_copy(
            lat_hbm.at[r, cols, :], buf.at[slot], sem.at[slot]
        )

    def start_first(r, slot):
        @pl.when(r < nb)
        def _start():
            copy(r, span(r)[2], slot).start()

    def write_back(r, g, c, slot):
        """VMEM -> HBM of the aligned tile that holds column ``c`` of
        block ``g``."""
        t0 = pl.multiple_of(c // TILE * TILE, TILE)
        to = pl.multiple_of(g * block + t0, TILE)
        return pltpu.make_async_copy(
            buf.at[slot, pl.ds(t0, TILE), :],
            lat_out.at[r, pl.ds(to, TILE), :], wsem.at[0],
        )

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
            q = q_ref[j]                                 # (H, W)

            def trip(i, carry):
                slot = jax.lax.rem(slot0 + i, 2)
                g = g0 + i
                last = i + 1 == n
                c = hi - 1 - g * block                   # the new token

                @pl.when(i + 1 < n)
                def _next_block():
                    copy(r, g + 1, 1 - slot).start()

                @pl.when(last)
                def _next_row():
                    start_first(next_row(r + 1), 1 - slot)

                copy(r, g, slot).wait()

                @pl.when(last)
                def _append():
                    t0 = pl.multiple_of(c // TILE * TILE, TILE)
                    tile = buf[slot, pl.ds(t0, TILE), :].astype(jnp.float32)
                    hit = t0 + jax.lax.broadcasted_iota(
                        jnp.int32, tile.shape, 0
                    ) == c
                    buf[slot, pl.ds(t0, TILE), :] = jnp.where(
                        hit, new_ref[j], tile
                    ).astype(buf.dtype)
                    write_back(r, g, c, slot).start()

                blk = buf[slot]                          # (block, W)
                s = jax.lax.dot_general(
                    q, blk, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32, precision=precision,
                )                                        # (H, block)
                cols = g * block + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1
                )
                seen = (cols >= lo) & (cols < hi)
                s = jnp.where(seen, s, NEG_INF)
                m = m_ref[:, :1]
                m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
                fade = jnp.exp(m - m_new)
                l_ref[:] = jnp.broadcast_to(
                    fade * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True),
                    l_ref.shape,
                )
                m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
                acc_ref[:] = fade * acc_ref[:] + jax.lax.dot_general(
                    p.astype(blk.dtype), blk[:, :dc],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32, precision=precision,
                )

                # the tile has left this slot before the next row's
                # first trip starts a fetch into it
                @pl.when(last)
                def _written():
                    write_back(r, g, c, slot).wait()

                return carry

            jax.lax.fori_loop(0, n, trip, 0)
            slot_ref[0] = jax.lax.rem(slot0 + n, 2)
            l = l_ref[:, :1]
            o_ref[j] = acc_ref[:] / jnp.where(l == 0.0, 1.0, l)

        return carry

    jax.lax.fori_loop(0, rows, row, 0)


@functools.partial(jax.jit, static_argnames=("dc", "interpret"))
def _walk(q, new, cache, start, stop, *, dc, interpret):
    b, h, w = q.shape
    l_buf = cache.shape[1]
    block = block_of(l_buf)
    rows = max(d for d in range(1, min(b, ROWS_PER_STEP) + 1) if b % d == 0)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    precision = (jax.lax.Precision.HIGHEST
                 if cache.dtype == jnp.float32 else None)
    return pl.pallas_call(
        functools.partial(_kernel, block=block, dc=dc, precision=precision),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b // rows,),
            in_specs=[
                pl.BlockSpec((rows, h, w), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((rows, 1, w), lambda i, *_: (i, 0, 0)),
                hbm,
            ],
            out_specs=[
                pl.BlockSpec((rows, h, dc), lambda i, *_: (i, 0, 0)), hbm,
            ],
            scratch_shapes=[
                # two block slots: one computes while the other lands
                pltpu.VMEM((2, block, w), cache.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),  # slot of the next first block
                pltpu.VMEM((h, dc), jnp.float32),
                pltpu.VMEM((h, LANES), jnp.float32),
                pltpu.VMEM((h, LANES), jnp.float32),
                pltpu.SemaphoreType.DMA((1,)),  # the write-back
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, dc), jnp.float32),
            jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        ],
        # operands 0 and 1 are the prefetched windows
        input_output_aliases={4: 1},
        # the slot parity and the prefetched first block carry from one
        # grid step to the next: the steps run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="latent_decode",
    )(start, stop, q, new, cache)


def latent_decode(
    q: jax.Array, new: jax.Array, cache: jax.Array, start: jax.Array,
    stop: jax.Array, *, dc: int, interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """One token a row.  ``q`` (B, H, W): the heads' absorbed queries,
    scaled; ``new`` (B, W): the token's latent; ``cache`` (B, L, W),
    ``L`` whole blocks (:func:`buffer_len`); ``start``, ``stop`` (B,)
    int32: a row attends slots ``[start, stop)`` and its token is
    written at ``stop - 1`` first.  The first ``dc`` numbers of a cached
    row are its value.  The cache is updated in place
    (``input_output_aliases``); a row with an empty window is not
    written and returns zeros.  Returns the heads' weighted sums
    (B, H, dc) float32 and the cache."""
    if interpret is None:
        interpret = interpret_default()
    l_buf = cache.shape[1]
    if l_buf % block_of(l_buf):
        raise ValueError(
            f"a latent cache of {l_buf} slots is not whole blocks of "
            f"{block_of(l_buf)}: allocate buffer_len(length)"
        )
    return _walk(
        q.astype(cache.dtype), new.astype(jnp.float32)[:, None], cache,
        start.astype(jnp.int32), stop.astype(jnp.int32), dc=dc,
        interpret=interpret,
    )
