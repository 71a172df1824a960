"""Kimi Delta Attention: a layer whose per-slot cache is a gated
delta-rule state.

A head keeps a ``key x value`` matrix ``S`` (float32) and, for token
``t`` with decay ``alpha_t`` (one number a key CHANNEL), write strength
``beta_t``, key ``k_t``, value ``v_t`` and query ``q_t``::

    S' = Diag(alpha_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T       o_t = S_t^T q_t

so the state first forgets by channel, then takes back what it already
says of ``k_t`` (the erase) as it writes ``v_t``.  ``q``, ``k`` and ``v``
come through a depthwise causal convolution of ``conv`` taps along the
sequence and SiLU; ``q`` and ``k`` are L2-normed a head (``q`` scaled by
``head_dim ** -0.5``); ``log alpha = -exp(A_log) * softplus(low-rank(h)
+ dt_bias)`` in float32; the output is RMS-normed a head, gated by the
logistic function of a low-rank projection and projected out.  A slot's
cache is ``S`` and the convolution's last ``conv - 1`` inputs, whatever
its context.  One module, three forms of the one function:

(a) no cache (``decode=False``): the chunked form from a zero state;
(b) a chunk against the carried state (``decode=True``): blocks of at
    most ``BLOCK`` tokens.  Inside a block the tokens' corrections
    ``u = beta (v - S'^T k)`` solve one unit-triangular system (each
    token's erase sees the writes before it), after which what the
    state gives the block's queries, the block's own causal part and
    the state's advance are plain matrix products.  The systems of all
    blocks are solved together; only the products with the state walk
    the blocks in order.  Tokens the chunk's slice of ``kv_mask`` leaves
    out (left pads) enter the convolution as zeros, write nothing
    (``beta`` 0) and decay nothing (``alpha`` 1);
(c) one token a row under per-row cursors (``cache_cursor``): the
    kernel ``ops/pallas/kda.py``, one pass over the state in place.  A
    row whose ``kv_mask`` is all false holds no request: its state is
    neither read nor written.

The recurrence is float32 throughout, in all three forms: the decays,
the triangular solve, every product with the state (``highest``
precision: a chunk's matrix products are ~50 GFLOP at the published
widths, nothing beside its projections).  The projections, the
convolution's inputs and its cached tail are in the module's ``dtype``.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from mlcomp_tpu.models.counts import count_group, state_rows_block
from mlcomp_tpu.models.transformer import RMSNorm, rmsnorm
from mlcomp_tpu.ops.pallas.kda import kda_step, state_bytes_moved

# what a call sows into the ``counters`` collection: rows whose state
# the single-token kernel updated, the bytes that pass moved, tokens
# absorbed by chunk calls, 1 (the call)
COUNTS = count_group("kda", (
    ("state_rows",
     "Rows whose delta-rule state a single-token step updated, "
     "summed over layers and steps"),
    ("state_bytes",
     "Bytes those passes moved (ops/pallas/kda.py "
     "state_bytes_moved): each row's states read and written once"),
    ("chunk_tokens",
     "Tokens chunk calls absorbed into a state, summed over layers"),
    ("layer_calls", "KDA-layer calls (layers x steps, and chunks)"),
), block=state_rows_block)

# tokens a block of the chunked form, and rows a sub-block of its
# triangular solve
BLOCK = 64
SUB = 16
L2_EPS = 1e-6
# exp(A_log), first head to last: with softplus(0) = 0.69 a token's
# decay runs from ~0.95 to ~0.9997 across the heads (memories of ~20 to
# ~3,000 tokens) until a checkpoint says otherwise.  A rate drawn around
# 1 forgets in two tokens
DECAY_RATES = (0.074, 0.00043)
HI = jax.lax.Precision.HIGHEST


def a_log_init(heads: int) -> jax.Array:
    lo, hi = (math.log(r) for r in DECAY_RATES)
    return jnp.linspace(lo, hi, heads, dtype=jnp.float32)


def l2_normed(x: jax.Array) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def short_conv(x, tail, taps):
    """Depthwise causal convolution along the sequence: ``x`` (B, S, C),
    ``tail`` (B, T - 1, C) the inputs before it, ``taps`` (T, C) with
    the last tap on the current token.  Returns the outputs (B, S, C)
    float32 and the new tail."""
    t, s = taps.shape[0], x.shape[1]
    seen = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    out = sum(
        taps[i].astype(jnp.float32) * seen[:, i:i + s].astype(jnp.float32)
        for i in range(t)
    )
    return out, seen[:, s:]


def solve_unit_lower(system, rhs):
    """``X`` with ``system @ X = rhs``; ``system`` (..., C, C) unit
    lower-triangular, ``C`` whole sub-blocks of ``SUB`` rows.  Forward
    substitution twice over: the diagonal sub-blocks are inverted a row
    at a time (all of them together, ``SUB`` steps), then the
    sub-blocks' rows of ``X`` follow one another through plain matrix
    products.  On a v5e the library's triangular solve took 3.5 ms for
    a 2,048-token chunk's 1,024 systems of 64, this 1.1 (PR 41)."""
    c = system.shape[-1]
    nsub, lead = c // SUB, system.shape[:-2]
    blocks = (system - jnp.eye(c)).reshape(lead + (nsub, SUB, nsub, SUB))
    # row r of every diagonal sub-block side by side: (..., SUB, nsub, SUB)
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(nsub)], axis=-2)

    def row(j, inv):
        # rows below j are still zero, and row j reads rows above it
        r = jax.lax.dynamic_index_in_dim(diag, j, len(lead), keepdims=False)
        new = jax.nn.one_hot(j, SUB) - jnp.einsum(
            "...il,...lic->...ic", r, inv, precision=HI)
        return jax.lax.dynamic_update_index_in_dim(inv, new, j, len(lead))

    inv = jax.lax.fori_loop(0, SUB, row, jnp.zeros_like(diag))
    xs = []
    for i in range(nsub):
        b = rhs[..., i * SUB:(i + 1) * SUB, :]
        for j in range(i):
            b = b - jnp.einsum("...rc,...cd->...rd", blocks[..., i, :, j, :],
                               xs[j], precision=HI)
        xs.append(jnp.einsum("...rc,...cd->...rd", inv[..., :, i, :], b,
                             precision=HI))
    return jnp.concatenate(xs, axis=-2)


def delta_chunks(q, k, v, log_a, beta, state):
    """The recurrence over a chunk, block by block.  ``q``, ``k``
    (B, S, N, dk), ``v`` (B, S, N, dv), ``log_a`` (B, S, N, dk),
    ``beta`` (B, S, N), ``state`` (B, N, dk, dv): all float32.  Returns
    the outputs (B, S, N, dv) and the state after the chunk."""
    b, s, n, dk = q.shape
    dv = v.shape[-1]
    c = min(BLOCK, -(-s // SUB) * SUB)
    nb = -(-s // c)

    def blocks(a):
        # a block short of tokens is filled with ones that write
        # nothing (beta 0) and decay nothing (log alpha 0)
        a = jnp.pad(a, ((0, 0), (0, nb * c - s)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((b, nb, c) + a.shape[2:])
        return jnp.moveaxis(a, (1, 3), (0, 2))           # (nb, B, N, C, ..)

    q, k, v, log_a = blocks(q), blocks(k), blocks(v), blocks(log_a)
    beta = blocks(beta)[..., None]                       # (nb, B, N, C, 1)
    cum = jnp.cumsum(log_a, axis=3)                      # inclusive
    total = cum[:, :, :, -1:]
    k_in, q_in = k * jnp.exp(cum), q * jnp.exp(cum)      # decayed from the
    k_out = k * jnp.exp(-cum)                            # block's start
    k_end = k * jnp.exp(total - cum)                     # ... to its end
    t = jnp.arange(c)
    # token j's erase reads what tokens l < j wrote, decayed from l to j
    erase = jnp.einsum("...jc,...lc->...jl", k_in, k_out, precision=HI)
    system = jnp.eye(c) + beta * jnp.where(t[:, None] > t[None, :], erase, 0)
    w = solve_unit_lower(system, beta * jnp.concatenate([v, k_in], axis=-1))
    w_v, w_k = w[..., :dv], w[..., dv:]
    own = jnp.where(
        t[:, None] >= t[None, :],
        jnp.einsum("...ic,...jc->...ij", q_in, k_out, precision=HI), 0,
    )

    def block(state, xs):
        w_v, w_k, q_in, own, k_end, keep = xs
        u = w_v - jnp.einsum("bnjc,bncd->bnjd", w_k, state, precision=HI)
        out = jnp.einsum("bnic,bncd->bnid", q_in, state, precision=HI) \
            + jnp.einsum("bnij,bnjd->bnid", own, u, precision=HI)
        state = keep * state \
            + jnp.einsum("bnjc,bnjd->bncd", k_end, u, precision=HI)
        return state, out

    keep = jnp.exp(total)[:, :, :, 0, :, None]           # (nb, B, N, dk, 1)
    state, out = jax.lax.scan(
        block, state, (w_v, w_k, q_in, own, k_end, keep)
    )
    out = jnp.moveaxis(out, (0, 2), (1, 3)).reshape(b, nb * c, n, dv)
    return out[:, :s], state


class KimiDeltaAttention(nn.Module):
    """Pre-norm KDA with ``SelfAttention``'s call signature and its
    projections' parameter names (``q``, ``k``, ``v``, ``out``, the norm
    ``RMSNorm_0``); beside them ``conv`` (taps, 3 streams' channels),
    the decay's ``decay_a`` / ``decay_b`` (hidden -> head_dim -> a
    number a key channel, float32), ``A_log`` (a head), ``dt_bias`` (a
    channel), ``beta`` (hidden -> a head), the output gate's ``gate_a``
    / ``gate_b`` (hidden -> head_dim -> a value channel) and ``o_norm``
    (a learned vector a head width)."""

    hidden: int
    heads: int
    head_dim: int
    dtype: jnp.dtype
    conv: int = 4

    @nn.compact
    def __call__(self, x, positions, decode=False, kv_mask=None,
                 cache_cursor=None):
        del positions                                    # no rotation
        dh, n = self.head_dim, self.heads
        b, s = x.shape[:2]
        h = RMSNorm(self.dtype)(x)
        dense = lambda width, name, dtype=self.dtype: nn.Dense(  # noqa: E731
            width, use_bias=False, dtype=dtype, name=name
        )
        streams = jnp.concatenate(
            [dense(n * dh, name)(h) for name in ("q", "k", "v")], axis=-1
        )
        taps = self.param(
            "conv", nn.initializers.normal(self.conv ** -0.5),
            (self.conv, 3 * n * dh), jnp.float32,
        )
        with jax.named_scope("kda.gate"):
            h32 = h.astype(jnp.float32)
            f32 = jnp.float32
            rate = jnp.exp(self.param("A_log", lambda *_: a_log_init(n)))
            dt_bias = self.param(
                "dt_bias", nn.initializers.zeros, (n * dh,), f32
            )
            lift = dense(n * dh, "decay_b", f32)(dense(dh, "decay_a", f32)(h32))
            log_a = -rate[:, None] * jax.nn.softplus(
                (lift + dt_bias).reshape(b, s, n, dh)
            )
            beta = jax.nn.sigmoid(dense(n, "beta", f32)(h32))  # (B, S, N)
            gate = jax.nn.sigmoid(
                dense(n * dh, "gate_b")(dense(dh, "gate_a")(h))
            ).reshape(b, s, n, dh)
        if decode:
            state = self.variable(
                "cache", "state", jnp.zeros, (b, n, dh, dh), jnp.float32
            )
            tail = self.variable(
                "cache", "conv", jnp.zeros,
                (b, self.conv - 1, 3 * n * dh), self.dtype,
            )
            index = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
            )
        if self.is_initializing():
            # init traces this module at the whole buffer's length only
            # to learn the cache's shapes: the variables exist
            out = jnp.zeros((b, s, n, dh), jnp.float32)
            counts = jnp.zeros(len(COUNTS.entries), jnp.float32)
        elif not decode:
            valid = None if kv_mask is None else kv_mask[:, :s]
            zeros = jnp.zeros((b, self.conv - 1, 3 * n * dh), self.dtype)
            out, _, _ = self._chunk(
                streams, zeros, taps, log_a, beta, valid,
                jnp.zeros((b, n, dh, dh), jnp.float32),
            )
        elif cache_cursor is not None:
            out, counts = self._step(
                streams, taps, log_a, beta, kv_mask, state, tail
            )
        else:
            i = index.value
            index.value = i + s
            valid = None if kv_mask is None else \
                jax.lax.dynamic_slice_in_dim(kv_mask, i, s, axis=1)
            out, state.value, tail.value = self._chunk(
                streams, tail.value, taps, log_a, beta, valid, state.value,
            )
            tokens = jnp.float32(b * s) if valid is None \
                else jnp.sum(valid).astype(jnp.float32)
            counts = jnp.stack([
                jnp.float32(0.0), jnp.float32(0.0), tokens, jnp.float32(1.0),
            ])
        if decode:
            self.sow(
                "counters", COUNTS.name, counts,
                reduce_fn=lambda a, c: a + c,
                init_fn=lambda: jnp.zeros(len(COUNTS.entries), jnp.float32),
            )
        scale = self.param("o_norm", nn.initializers.ones, (dh,), jnp.float32)
        out = rmsnorm(out, scale, self.dtype) * gate
        return x + nn.DenseGeneral(
            self.hidden, axis=(-2, -1), use_bias=False, dtype=self.dtype,
            name="out",
        )(out)

    def _qkv(self, streams, tail, taps):
        """The three streams through the convolution and SiLU, as heads:
        ``q`` normed and scaled, ``k`` normed, ``v``; and the new tail."""
        b, s = streams.shape[:2]
        with jax.named_scope("kda.conv"):
            mixed, tail = short_conv(streams, tail, taps)
            q, k, v = jnp.split(
                nn.silu(mixed).reshape(b, s, 3 * self.heads, self.head_dim),
                3, axis=2,
            )
            q = l2_normed(q) * self.head_dim ** -0.5
            return q, l2_normed(k), v, tail

    def _chunk(self, streams, tail, taps, log_a, beta, valid, state):
        if valid is not None:
            streams = jnp.where(valid[..., None], streams, 0)
            log_a = jnp.where(valid[..., None, None], log_a, 0.0)
            beta = jnp.where(valid[..., None], beta, 0.0)
        q, k, v, tail = self._qkv(streams, tail, taps)
        with jax.named_scope("kda.chunk"):
            out, state = delta_chunks(q, k, v, log_a, beta, state)
        return out, state, tail

    def _step(self, streams, taps, log_a, beta, kv_mask, state, tail):
        b, s = streams.shape[:2]
        if s != 1:
            raise ValueError(
                "cache_cursor (per-row cursors) is the single-token "
                f"step's contract; got a chunk of {s} tokens"
            )
        live = jnp.ones((b,), bool) if kv_mask is None \
            else jnp.any(kv_mask, axis=1)
        q, k, v, tail.value = self._qkv(streams, tail.value, taps)
        with jax.named_scope("kda.step"):
            out, state.value = kda_step(
                q[:, 0], k[:, 0], v[:, 0], log_a[:, 0], beta[:, 0], live,
                state.value,
            )
        rows = jnp.sum(live).astype(jnp.float32)
        per_row = float(state_bytes_moved(
            1, self.heads, self.head_dim, self.head_dim
        ))
        counts = jnp.stack([
            rows, rows * per_row, jnp.float32(0.0), jnp.float32(1.0),
        ])
        return out[:, None], counts
