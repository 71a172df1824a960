"""Gated short convolution: a layer whose per-slot cache is its last
``taps - 1`` inputs.

With ``h`` the normed layer input and ``taps`` numbers a channel::

    [B | C | X] = h W_in          (hidden -> 3 x hidden, in that order)
    u = B * X
    c_t = sum_j w_j * u_{t - taps + 1 + j}      (depthwise, causal)
    y = (C * c) W_out

no bias anywhere and no activation after the convolution.  What a slot
keeps is the last ``taps - 1`` values of ``u`` (2 x hidden numbers at 3
taps), whatever its context.  The convolution is ``kda.short_conv``
(float32 accumulation); the projections, ``u`` and the cached tail are
in the module's ``dtype``.  One module, three forms of the one function:

(a) no cache (``decode=False``): the convolution from a zero tail;
(b) a chunk against the carried tail (``decode=True``) under the one
    ``cache_index``.  Tokens the chunk's slice of ``kv_mask`` leaves out
    (left pads) enter as ``u = 0``, not as ``x = 0``: ``W_in`` of a
    pad's embedding is not zero, and a zero ``u`` is what the tail of a
    fresh slot holds;
(c) one token a row under per-row cursors (``cache_cursor``).  A row
    whose ``kv_mask`` is all false holds no request: its tail stays as
    it is.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from mlcomp_tpu.models.counts import count_group, state_rows_block
from mlcomp_tpu.models.kda import short_conv
from mlcomp_tpu.models.transformer import RMSNorm

# what a call sows into the ``counters`` collection: rows whose tail a
# single-token step moved on, the bytes of those tails read and
# written, tokens absorbed by chunk calls, 1 (the call)
COUNTS = count_group("conv", (
    ("state_rows",
     "Rows whose convolution tail a single-token step moved on, "
     "summed over layers and steps"),
    ("state_bytes",
     "Bytes of those tails, each read and written once"),
    ("chunk_tokens",
     "Tokens chunk calls passed through a tail, summed over layers"),
    ("layer_calls", "Conv-layer calls (layers x steps, and chunks)"),
), block=state_rows_block)


class GatedShortConv(nn.Module):
    """Pre-norm gated short convolution with ``SelfAttention``'s call
    signature; parameters ``in`` (hidden -> 3 x hidden), ``conv`` (taps,
    hidden; float32), ``out`` and the norm ``RMSNorm_0``."""

    hidden: int
    dtype: jnp.dtype
    taps: int = 3

    @nn.compact
    def __call__(self, x, positions, decode=False, kv_mask=None,
                 cache_cursor=None):
        del positions                                    # no rotation
        b, s = x.shape[:2]
        c = self.hidden
        h = RMSNorm(self.dtype)(x)
        dense = lambda width, name: nn.Dense(  # noqa: E731
            width, use_bias=False, dtype=self.dtype, name=name
        )
        with jax.named_scope("conv.project"):
            gate_in, gate_out, value = jnp.split(dense(3 * c, "in")(h), 3, -1)
            u = gate_in * value
        taps = self.param(
            "conv", nn.initializers.normal(self.taps ** -0.5),
            (self.taps, c), jnp.float32,
        )
        zeros = jnp.zeros((b, self.taps - 1, c), self.dtype)
        if decode:
            tail = self.variable("cache", "conv", lambda: zeros)
            index = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
            )
        if self.is_initializing():
            # init traces this module at the whole buffer's length only
            # to learn the cache's shapes: the variables exist
            mixed = jnp.zeros((b, s, c), jnp.float32)
            counts = jnp.zeros(len(COUNTS.entries), jnp.float32)
        elif not decode:
            valid = None if kv_mask is None else kv_mask[:, :s]
            mixed, _ = self._chunk(u, zeros, taps, valid)
        elif cache_cursor is not None:
            mixed, counts = self._step(u, taps, kv_mask, tail)
        else:
            i = index.value
            index.value = i + s
            valid = None if kv_mask is None else \
                jax.lax.dynamic_slice_in_dim(kv_mask, i, s, axis=1)
            mixed, tail.value = self._chunk(u, tail.value, taps, valid)
            tokens = jnp.float32(b * s) if valid is None \
                else jnp.sum(valid).astype(jnp.float32)
            counts = jnp.stack([
                jnp.float32(0.0), jnp.float32(0.0), tokens, jnp.float32(1.0),
            ])
        if decode:
            self.sow(
                "counters", COUNTS.name, counts,
                reduce_fn=lambda a, n: a + n,
                init_fn=lambda: jnp.zeros(len(COUNTS.entries), jnp.float32),
            )
        return x + dense(c, "out")(gate_out * mixed.astype(self.dtype))

    def _chunk(self, u, tail, taps, valid):
        with jax.named_scope("conv.mix"):
            if valid is not None:
                u = jnp.where(valid[..., None], u, 0)
            return short_conv(u, tail, taps)

    def _step(self, u, taps, kv_mask, tail):
        b, s = u.shape[:2]
        if s != 1:
            raise ValueError(
                "cache_cursor (per-row cursors) is the single-token "
                f"step's contract; got a chunk of {s} tokens"
            )
        live = jnp.ones((b,), bool) if kv_mask is None \
            else jnp.any(kv_mask, axis=1)
        with jax.named_scope("conv.step"):
            mixed, moved = short_conv(u, tail.value, taps)
            tail.value = jnp.where(live[:, None, None], moved, tail.value)
        rows = jnp.sum(live).astype(jnp.float32)
        per_row = float(2 * tail.value[0].size * tail.value.dtype.itemsize)
        counts = jnp.stack([
            rows, rows * per_row, jnp.float32(0.0), jnp.float32(1.0),
        ])
        return mixed, counts
