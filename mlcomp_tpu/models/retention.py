"""Power retention: a layer whose per-slot cache is a recurrent state.

Query ``t`` weighs token ``s <= t`` by ``(q_t . k_s)^2`` times the
product of the gates between them, and the output is the weighted mean
of the values (a normaliser, no softmax).  With ``phi`` the symmetric
second power of a head (``<phi(q), phi(k)> = (q . k)^2``) the same
function is a recurrence over a state of fixed size::

    S_t = g_t S_{t-1} + phi(k_t) v_t^T      z_t = g_t z_{t-1} + phi(k_t)
    o_t = S_t^T phi(q_t) / (z_t . phi(q_t) + eps)

so a slot's cache is ``S`` and ``z`` whatever its context, not keys and
values a token.  One module, three forms of the one function:

(a) no cache (``decode=False``): the masked quadratic form;
(b) a chunk against the carried state (``decode=True``): what the state
    gives the chunk's queries plus the chunk's own causal part, then
    the state advanced over the chunk.  Tokens the chunk's slice of
    ``kv_mask`` leaves out (left pads) add nothing and decay nothing;
(c) one token a row under per-row cursors (``cache_cursor``): the
    kernel ``ops/pallas/retention.py``, one pass over the state in
    place.  A row whose ``kv_mask`` is all false holds no request: its
    state is neither read nor written.

**The state's layout.**  ``phi`` is laid out in slabs of one head width:
slab ``r`` of ``phi(x)`` is ``c_r * x * roll(x, r)`` for ``r`` in
``0 .. head_dim / 2``.  Slab 0 holds the squares; slab ``r`` every
unordered pair at circular distance ``r`` once, with ``c_r = sqrt 2``;
the last slab holds each opposite pair twice, with ``c = 1``.  That is
``(head_dim / 2 + 1) * head_dim`` entries (8,320 at 128; the least a
symmetric second power needs is 8,256), every slab a whole lane tile,
and ``phi`` costs one lane rotation and two multiplies a slab, inside
the kernel, never a gather.  The cache keeps ``state``
``(B, kv_heads, D, head_dim)`` float32, row ``r * head_dim + d`` the
value dimension ``d`` of slab ``r`` and the lanes the slab's entries,
``norm`` ``(B, kv_heads, D)`` float32, and the scalar ``cache_index``
(the chunk form's place in ``kv_mask``).

Products of ``phi`` take their operands in the module's ``dtype``
(bfloat16 when served, float32 in the tests) and accumulate in float32;
the state, the gate and the normaliser are float32 throughout.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from mlcomp_tpu.models.counts import count_group, state_rows_block
from mlcomp_tpu.models.transformer import (
    RMSNorm,
    RopeSpec,
    apply_rope,
    apply_rope_spec,
    rmsnorm,
)
from mlcomp_tpu.ops.pallas.retention import (
    expanded_width,
    retention_step,
    slab_weights,
    slabs,
    state_bytes_moved,
)

# what a call sows into the ``counters`` collection: rows whose state
# the single-token kernel updated, the bytes that walk moved, tokens
# absorbed by chunk calls, 1 (the call)
COUNTS = count_group("retention", (
    ("state_rows",
     "Rows whose state a single-token step updated, summed over "
     "layers and steps"),
    ("state_bytes",
     "Bytes those walks moved (ops/pallas/retention.py "
     "state_bytes_moved): each row's state read and written once"),
    ("chunk_tokens",
     "Tokens chunk calls absorbed into a state, summed over layers"),
    ("layer_calls", "Retention-layer calls (layers x steps, and chunks)"),
), block=state_rows_block)

EPS = 1e-6


def expand_slab(x: jax.Array, r, c) -> jax.Array:
    """Slab ``r`` of ``phi(x)`` along the last axis, float32."""
    x = x.astype(jnp.float32)
    return c * x * jnp.roll(x, r, axis=-1)


def _causal_part(q, k, v, cum, dtype):
    """The chunk's own tokens: ``q`` (B, S, N, G, dh), ``k``/``v``
    (B, S, N, dh), ``cum`` (B, S, N) the inclusive running sum of the
    log gates.  Returns the numerator (B, S, N, G, dh) and the
    denominator (B, S, N, G), float32."""
    s = q.shape[1]
    dots = jnp.einsum("btngd,bund->bngtu", q.astype(dtype), k.astype(dtype),
                      preferred_element_type=jnp.float32)
    t = jnp.arange(s)
    seen = t[:, None] >= t[None, :]
    cum_n = cum.transpose(0, 2, 1)                       # (B, N, S)
    decay = jnp.where(
        seen, cum_n[:, :, :, None] - cum_n[:, :, None, :], -jnp.inf
    )
    a = dots * dots * jnp.exp(decay)[:, :, None]
    num = jnp.einsum("bngtu,bund->btngd", a.astype(dtype), v.astype(dtype),
                     preferred_element_type=jnp.float32)
    return num, a.sum(-1).transpose(0, 3, 1, 2)


def _state_part(q, k, v, cum, state, norm, dtype):
    """What the carried ``state`` (B, N, R, dh, dh) and ``norm``
    (B, N, R, dh) give the chunk's queries, and both advanced over the
    chunk: a loop over the slabs, so ``phi`` of the chunk is never
    whole in memory."""
    dh = q.shape[-1]
    weights = jnp.asarray(slab_weights(dh))
    total = cum[:, -1]                                   # (B, N)
    # a key's weight in the state at the chunk's end
    carry_k = jnp.exp(total[:, None] - cum)[..., None]   # (B, S, N, 1)
    keep = jnp.exp(total)

    def slab(r, acc):
        num, den, state, norm = acc
        c = weights[r]
        with jax.named_scope("retention.expand"):
            fq = expand_slab(q, r, c)                    # (B, S, N, G, dh)
            fk = expand_slab(k, r, c) * carry_k          # (B, S, N, dh)
        s_r = jax.lax.dynamic_index_in_dim(state, r, 2, keepdims=False)
        z_r = jax.lax.dynamic_index_in_dim(norm, r, 2, keepdims=False)
        num = num + jnp.einsum(
            "bsngi,bndi->bsngd", fq.astype(dtype), s_r.astype(dtype),
            preferred_element_type=jnp.float32)
        den = den + jnp.einsum("bsngi,bni->bsng", fq, z_r,
                               precision=jax.lax.Precision.HIGHEST)
        s_r = keep[..., None, None] * s_r + jnp.einsum(
            "bsnd,bsni->bndi", v.astype(dtype), fk.astype(dtype),
            preferred_element_type=jnp.float32)
        z_r = keep[..., None] * z_r + fk.sum(1)
        state = jax.lax.dynamic_update_index_in_dim(state, s_r, r, 2)
        norm = jax.lax.dynamic_update_index_in_dim(norm, z_r, r, 2)
        return num, den, state, norm

    b, s, n, g, _ = q.shape
    zeros = (jnp.zeros((b, s, n, g, dh), jnp.float32),
             jnp.zeros((b, s, n, g), jnp.float32))
    num, den, state, norm = jax.lax.fori_loop(
        0, slabs(dh), slab, zeros + (state, norm)
    )
    reach = jnp.exp(cum)[..., None]                      # (B, S, N, 1)
    return num * reach[..., None], den * reach, state, norm


class PowerRetention(nn.Module):
    """Pre-norm power retention with ``SelfAttention``'s call
    signature and its projections' parameter names (``q``, ``k``,
    ``v``, ``out``, the norm ``RMSNorm_0``); beside them ``gate``
    (hidden -> one log-gate a KV head, float32, with a bias) and, with
    ``qk_norm``, ``q_norm`` / ``k_norm`` (a learned vector a head
    width)."""

    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    dtype: jnp.dtype
    rope: Optional[RopeSpec] = None
    qk_norm: bool = True

    @nn.compact
    def __call__(self, x, positions, decode=False, kv_mask=None,
                 cache_cursor=None):
        dh, n = self.head_dim, self.kv_heads
        g = self.heads // n
        h = RMSNorm(self.dtype)(x)
        proj = lambda heads, name: nn.DenseGeneral(  # noqa: E731
            (heads, dh), use_bias=False, dtype=self.dtype, name=name
        )(h)
        q, k, v = proj(self.heads, "q"), proj(n, "k"), proj(n, "v")
        with jax.named_scope("retention.gate"):
            # memories of ~20 to ~3,000 tokens until a checkpoint says
            # otherwise: a bias drawn around 0 forgets in two tokens
            gamma = nn.Dense(
                n, dtype=jnp.float32, name="gate",
                bias_init=lambda *_: jnp.linspace(3.0, 8.0, n),
            )(h.astype(jnp.float32))
            log_g = jax.nn.log_sigmoid(gamma)            # (B, S, N)
        if self.qk_norm:
            scale = lambda name: self.param(  # noqa: E731
                name, nn.initializers.ones, (dh,), jnp.float32
            )
            q = rmsnorm(q, scale("q_norm"), self.dtype)
            k = rmsnorm(k, scale("k_norm"), self.dtype)
        if self.rope is None:
            q, k = apply_rope(q, positions), apply_rope(k, positions)
        else:
            q = apply_rope_spec(q, positions, self.rope)
            k = apply_rope_spec(k, positions, self.rope)
        b, s = x.shape[:2]
        q = q.reshape(b, s, n, g, dh)
        if not decode:
            valid = None if kv_mask is None else kv_mask[:, :s]
            out, counts = self._fresh(q, k, v, log_g, valid), None
        else:
            out, counts = self._cached(q, k, v, log_g, kv_mask, cache_cursor)
            self.sow(
                "counters", COUNTS.name, counts,
                reduce_fn=lambda a, c: a + c,
                init_fn=lambda: jnp.zeros(len(COUNTS.entries), jnp.float32),
            )
        out = out.reshape(b, s, self.heads, dh).astype(self.dtype)
        return x + nn.DenseGeneral(
            self.hidden, axis=(-2, -1), use_bias=False, dtype=self.dtype,
            name="out",
        )(out)

    @staticmethod
    def _masked(k, log_g, valid):
        """Tokens outside ``valid`` (B, S) add nothing (a zero key) and
        decay nothing (a gate of 1)."""
        if valid is None:
            return k, log_g
        return (jnp.where(valid[..., None, None], k, 0),
                jnp.where(valid[..., None], log_g, 0.0))

    def _fresh(self, q, k, v, log_g, valid):
        k, log_g = self._masked(k, log_g, valid)
        with jax.named_scope("retention.chunk"):
            num, den = _causal_part(
                q, k, v, jnp.cumsum(log_g, axis=1), self.dtype
            )
            return num / (den[..., None] + EPS)

    def _cached(self, q, k, v, log_g, kv_mask, cache_cursor):
        b, s, n, g, dh = q.shape
        width = expanded_width(dh)
        state = self.variable(
            "cache", "state", jnp.zeros, (b, n, width, dh), jnp.float32
        )
        norm = self.variable(
            "cache", "norm", jnp.zeros, (b, n, width), jnp.float32
        )
        index = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
        )
        if cache_cursor is not None:
            if s != 1:
                raise ValueError(
                    "cache_cursor (per-row cursors) is the single-token "
                    f"step's contract; got a chunk of {s} tokens"
                )
            live = jnp.ones((b,), bool) if kv_mask is None \
                else jnp.any(kv_mask, axis=1)
            with jax.named_scope("retention.step"):
                out, state.value, norm.value = retention_step(
                    q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], live,
                    state.value, norm.value, eps=EPS,
                    product_dtype=self.dtype,
                )
            rows = jnp.sum(live).astype(jnp.float32)
            counts = jnp.stack([
                rows, rows * float(state_bytes_moved(1, n, dh)),
                jnp.float32(0.0), jnp.float32(1.0),
            ])
            return out[:, None], counts
        i = index.value
        index.value = i + s
        valid = None if kv_mask is None else jax.lax.dynamic_slice_in_dim(
            kv_mask, i, s, axis=1
        )
        k, log_g = self._masked(k, log_g, valid)
        cum = jnp.cumsum(log_g, axis=1)
        r = slabs(dh)
        with jax.named_scope("retention.chunk"):
            num, den = _causal_part(q, k, v, cum, self.dtype)
            num_s, den_s, new_state, new_norm = _state_part(
                q, k, v, cum, state.value.reshape(b, n, r, dh, dh),
                norm.value.reshape(b, n, r, dh), self.dtype,
            )
        state.value = new_state.reshape(b, n, width, dh)
        norm.value = new_norm.reshape(b, n, width)
        tokens = jnp.float32(b * s) if valid is None \
            else jnp.sum(valid).astype(jnp.float32)
        counts = jnp.stack([
            jnp.float32(0.0), jnp.float32(0.0), tokens, jnp.float32(1.0),
        ])
        return (num + num_s) / (den + den_s + EPS)[..., None], counts
