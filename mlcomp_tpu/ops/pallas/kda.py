"""Kimi Delta Attention's single-token step: one pass over the state.

A head's state ``S`` is a ``key x value`` matrix (128 x 128 at the
published widths, float32).  For every row that holds a request the
kernel reads each head's state once and writes it back where it was
(``input_output_aliases``)::

    S' = Diag(alpha) S                  decay by the key CHANNEL
    u  = beta (v - S'^T k)              what the state does not yet say of k
    S  = S' + k u^T                     the rank-one correction (the erase
                                        and the write in one)
    o  = S^T q                          read from the UPDATED state

All of it is exact float32 arithmetic on the VPU: the two products with
the state are a multiply and a sum down the sublanes, so nothing of the
state is ever rounded to a matmul's operand type.  ``alpha``, ``k`` and
``q`` run down the key axis (the sublanes): each comes in as a row and
is laid down the sublanes by one transpose of its sublane broadcast.

Rows without a request are skipped, not masked: the live rows are
compacted to the front of the grid through scalar prefetch and every
later grid step is pointed at the block the last live step left in
VMEM, so it moves nothing and computes nothing
(``ops/pallas/retention.py`` skips them the same way).

On a v5e, 112 live rows of 32 heads of 128 x 128 (470 MB moved) take
0.81 ms, 582 GB/s; XLA's lowering of the same step takes 1.06 ms
whatever the rows hold, and the kernel 0.63 ms with a quarter of the
rows empty (my chip runs, PR 41).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mlcomp_tpu.ops.pallas import interpret_default

SUBLANES = 8
# a grid step's share of a row's states: heads of one block
BLOCK_BYTES = 1 << 20
# rows of the per-head operand block: q, k, v, alpha, beta
Q, K, V, ALPHA, BETA = range(5)


def state_bytes_moved(rows, heads: int, key_dim: int, value_dim: int):
    """Bytes the step moves for ``rows`` live rows of one layer: each
    row's states, float32, read once and written once."""
    return rows * heads * key_dim * value_dim * 4 * 2


def heads_per_block(heads: int, key_dim: int, value_dim: int) -> int:
    """Heads a grid step takes: the most whose states are one block of
    at most ``BLOCK_BYTES``, dividing the head count."""
    most = max(1, BLOCK_BYTES // (key_dim * value_dim * 4))
    return max(d for d in range(1, min(heads, most) + 1) if heads % d == 0)


def _kernel(rows_ref, n_live_ref, x_ref, s_ref, o_ref, s_out):
    del rows_ref  # the index maps read it
    hb, dk, dv = s_ref.shape[1:]
    n_live = n_live_ref[0]

    def down(row):
        """A (1, dk) row laid down the key axis: [c, d] = row[c]."""
        return jnp.transpose(jnp.broadcast_to(row, (dv, dk)))

    @pl.when(pl.program_id(0) < n_live)
    def _live_row():
        def head(h, carry):
            x = x_ref[0, h]                              # (8, width)
            q, k = x[Q:Q + 1, :dk], x[K:K + 1, :dk]
            v, beta = x[V:V + 1, :dv], x[BETA:BETA + 1, :dv]
            k_down = down(k)
            s = down(x[ALPHA:ALPHA + 1, :dk]) * s_ref[0, h]
            u = beta * (v - jnp.sum(s * k_down, axis=0, keepdims=True))
            s = s + k_down * u
            s_out[0, h] = s
            o = jnp.sum(s * down(q), axis=0, keepdims=True)
            o_ref[0, h] = jnp.broadcast_to(o, (SUBLANES, dv))
            return carry

        # unrolled: one head's transposes run under the next one's
        # multiplies (0.98 -> 0.81 ms for 112 rows on a v5e, PR 41)
        jax.lax.fori_loop(0, hb, head, 0, unroll=True)

    @pl.when((n_live == 0) & (pl.program_id(0) == 0)
             & (pl.program_id(1) == 0))
    def _nothing_live():
        # every step then names this one block: hand it back as it came
        s_out[...] = s_ref[...]
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step(x, rows, n_live, state, *, interpret):
    b, n, dk, dv = state.shape
    hb = heads_per_block(n, dk, dv)
    groups = n // hb

    def at(j, g, rows_ref, n_live_ref):
        # past the live rows: the block the last live step left
        return rows_ref[j], jnp.where(j < n_live_ref[0], g, groups - 1), 0, 0

    block_bytes = hb * dk * dv * 4
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, groups),
            in_specs=[pl.BlockSpec((1, hb) + x.shape[2:], at),
                      pl.BlockSpec((1, hb, dk, dv), at)],
            out_specs=[pl.BlockSpec((1, hb, SUBLANES, dv), at),
                       pl.BlockSpec((1, hb, dk, dv), at)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, n, SUBLANES, dv), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # operands 0 and 1 are the prefetched row list and its length
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # a block of states in and out, each double-buffered
            vmem_limit_bytes=4 * block_bytes + (16 << 20),
        ),
        interpret=interpret,
        name="kda_step",
    )(rows, n_live, x, state)


def kda_step(
    q: jax.Array, k: jax.Array, v: jax.Array, log_a: jax.Array,
    beta: jax.Array, live: jax.Array, state: jax.Array, *,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """One token a row.  ``q``, ``k`` (B, N, dk) (normed and scaled by
    the caller), ``v`` (B, N, dv), ``log_a`` (B, N, dk) float32 log
    decays, ``beta`` (B, N), ``live`` (B,) bool, ``state``
    (B, N, dk, dv) float32, updated in place for the live rows.
    Returns the outputs (B, N, dv) float32 (zeros for a row that is not
    live) and the state."""
    if interpret is None:
        interpret = interpret_default()
    b, n, dk, dv = state.shape
    f32 = jnp.float32
    width = max(dk, dv)
    fit = lambda a: jnp.pad(  # noqa: E731
        a.astype(f32), ((0, 0), (0, 0), (0, width - a.shape[-1]))
    )
    # one block a head: q, k, v, the decay and beta across the lanes
    x = jnp.stack([
        fit(q), fit(k), fit(v), fit(jnp.exp(log_a.astype(f32))),
        jnp.broadcast_to(beta.astype(f32)[..., None], (b, n, width)),
    ], axis=2)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, SUBLANES - x.shape[2]), (0, 0)))
    n_live = jnp.sum(live).astype(jnp.int32)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    rows = order[jnp.minimum(jnp.arange(b), jnp.maximum(n_live - 1, 0))]
    out, state = _step(x, rows, n_live[None], state, interpret=interpret)
    return jnp.where(live[:, None, None], out[:, :, 0], 0.0), state
