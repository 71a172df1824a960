"""Power retention's single-token step: one pass over the state.

For every row that holds a request and every KV head the kernel walks
the head's state once, slab by slab (``models/retention.py`` has the
layout): a slab is read, decayed by the row's gate, the new key's slab
of ``phi`` times the value added, the group's query heads' weighted
sums and the normaliser accumulated FROM THE UPDATED slab, and the slab
written back where it was (``input_output_aliases``).  The state is
float32 and the update is exact float32 arithmetic on the VPU; the
readout is one MXU product a slab with operands in ``product_dtype``.
``phi`` is made in the kernel: a lane rotation and two multiplies a
slab, for the queries (one vreg) and the key.

Rows without a request are skipped, not masked: the live rows are
compacted to the front of the grid through scalar prefetch and every
later grid step is pointed at the block the last live step left in
VMEM, so it moves nothing and computes nothing.

An XLA lowering of the same step writes the new state and reads it
again for the query: half as many bytes again.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mlcomp_tpu.ops.pallas import interpret_default

SUBLANES = 8


def slabs(head_dim: int) -> int:
    """Slabs of one head width in ``phi`` of a head: the squares, one a
    circular distance, and the opposite pairs."""
    return head_dim // 2 + 1


def expanded_width(head_dim: int) -> int:
    """Entries of ``phi`` of one head, as the state lays them out."""
    return slabs(head_dim) * head_dim


def slab_weights(head_dim: int) -> np.ndarray:
    """``c_r``: 1 for the squares and for the opposite pairs (the
    rotation meets each of those twice), sqrt 2 for every slab
    between."""
    c = np.full((slabs(head_dim),), math.sqrt(2.0), np.float32)
    c[0] = c[-1] = 1.0
    return c


def state_bytes_moved(rows, kv_heads: int, head_dim: int):
    """Bytes the walk moves for ``rows`` live rows of one layer: each
    row's state and normaliser, float32, read once and written once."""
    per_head = expanded_width(head_dim) * (head_dim + 1)
    return rows * kv_heads * per_head * 4 * 2


def _kernel(rows_ref, n_live_ref, q_ref, kgv_ref, s_ref, z_ref,
            o_ref, s_out, z_out, *, weights, eps, product_dtype):
    del rows_ref  # the index maps read it
    dh = q_ref.shape[-1]
    n_live = n_live_ref[0]
    precision = (jax.lax.Precision.HIGHEST
                 if jnp.dtype(product_dtype) == jnp.float32 else None)

    @pl.when(pl.program_id(0) < n_live)
    def _live_row():
        q = q_ref[0, 0]                                  # (G8, dh)
        kgv = kgv_ref[0, 0]
        k, g, v = kgv[0:1], kgv[1:2], kgv[2:3]           # (1, dh) each
        # the value down the sublanes: [d, i] = v[d]
        v_cols = jnp.transpose(jnp.broadcast_to(v, (dh, dh)))
        num = jnp.zeros(q.shape, jnp.float32)
        den = jnp.zeros(q.shape, jnp.float32)
        for r, c in enumerate(weights):
            if r == 0:
                fq, fk = q * q, k * k
            else:
                fq = c * q * pltpu.roll(q, r, 1)
                fk = c * k * pltpu.roll(k, r, 1)
            rows = pl.ds(r * dh, dh)
            s = g * s_ref[0, 0, rows, :] + v_cols * fk   # [d, i]
            s_out[0, 0, rows, :] = s
            z = g * z_ref[0, 0, r:r + 1, :] + fk
            z_out[0, 0, r:r + 1, :] = z
            num += jax.lax.dot_general(
                fq.astype(product_dtype), s.astype(product_dtype),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=precision,
            )
            den += fq * z
        o_ref[0, 0] = num / (jnp.sum(den, axis=1, keepdims=True) + eps)

    @pl.when((n_live == 0) & (pl.program_id(0) == 0)
             & (pl.program_id(1) == 0))
    def _nothing_live():
        # every step then names this one block: hand it back as it came
        s_out[...] = s_ref[...]
        z_out[...] = z_ref[...]
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("eps", "product_dtype", "interpret")
)
def _step(q, kgv, rows, n_live, state, norm, *, eps, product_dtype,
          interpret):
    b, n, gp, dh = q.shape
    width = state.shape[2]

    def at(j, h, rows_ref, n_live_ref):
        # past the live rows: the block the last live step left
        return rows_ref[j], jnp.where(j < n_live_ref[0], h, n - 1), 0, 0

    small = pl.BlockSpec((1, 1, gp, dh), at)
    state_spec = pl.BlockSpec((1, 1, width, dh), at)
    norm_spec = pl.BlockSpec((1, 1, width // dh, dh), at)
    block_bytes = width * dh * 4
    return pl.pallas_call(
        functools.partial(
            _kernel, weights=tuple(float(c) for c in slab_weights(dh)),
            eps=eps, product_dtype=product_dtype,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n),
            in_specs=[small, small, state_spec, norm_spec],
            out_specs=[small, state_spec, norm_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct(norm.shape, norm.dtype),
        ],
        # operands 0 and 1 are the prefetched row list and its length
        input_output_aliases={4: 1, 5: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # a head's state in and out, each double-buffered
            vmem_limit_bytes=4 * block_bytes + (16 << 20),
        ),
        interpret=interpret,
        name="retention_step",
    )(rows, n_live, q, kgv, state, norm)


def retention_step(
    q: jax.Array, k: jax.Array, v: jax.Array, log_g: jax.Array,
    live: jax.Array, state: jax.Array, norm: jax.Array, *,
    eps: float, product_dtype=jnp.bfloat16,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One token a row.  ``q`` (B, N, G, dh): the query heads of each KV
    head; ``k``, ``v`` (B, N, dh); ``log_g`` (B, N) float32; ``live``
    (B,) bool; ``state`` (B, N, D, dh) and ``norm`` (B, N, D) float32,
    updated in place for the live rows.  Returns the outputs
    (B, N, G, dh) float32 (zeros for a row that is not live), the state
    and the normaliser."""
    if interpret is None:
        interpret = interpret_default()
    b, n, g, dh = q.shape
    gp = -(-g // SUBLANES) * SUBLANES
    f32 = jnp.float32
    qp = jnp.pad(q.astype(f32), ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    # the key, the gate across the lanes and the value: one block a head
    kgv = jnp.stack([
        k.astype(f32),
        jnp.broadcast_to(jnp.exp(log_g.astype(f32))[..., None], (b, n, dh)),
        v.astype(f32),
    ], axis=2)
    kgv = jnp.pad(kgv, ((0, 0), (0, 0), (0, SUBLANES - 3), (0, 0)))
    n_live = jnp.sum(live).astype(jnp.int32)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    rows = order[jnp.minimum(jnp.arange(b), jnp.maximum(n_live - 1, 0))]
    out, state, norm3 = _step(
        qp, kgv, rows, n_live[None], state,
        norm.reshape(b, n, -1, dh), eps=float(eps),
        product_dtype=jnp.dtype(product_dtype).name, interpret=interpret,
    )
    out = jnp.where(live[:, None, None, None], out[:, :, :g], 0.0)
    return out, state, norm3.reshape(norm.shape)
