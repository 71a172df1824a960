"""InternLM2's decoder in plain ``jax.numpy``: float32, every product at
``highest`` precision, no kernel, no cache, no batching tricks.

Written from the architecture (internlm/internlm2-1_8b, modeling_internlm2
as published): token embedding; per layer a pre-norm RMSNorm, grouped-
query attention with rotary embeddings (rotate-half convention) and a
causal mask, a residual, a second RMSNorm, a SwiGLU MLP
``w_down(silu(w_gate x) * w_up x)``, a residual; a final RMSNorm and an
untied output head.  No biases.

Departures from the published model, applied here because the program
under test fixes them in code (the configuration files list them):
``rope_theta`` and ``rms_norm_eps`` are arguments, and the benchmark
passes the program's 10000 and 1e-6 where InternLM2 publishes 1e6 and
1e-5.  InternLM2 stores q, k and v packed in one ``wqkv``; they are
separate leaves here — a layout, not arithmetic.

``round_fn`` rounds every product's operands (identity for the
reference itself); the training control passes a float8 rounding.
``kv_fn`` rounds the keys and values attention reads (identity for the
reference; serving's second control passes an int4 rounding).

This file is also the architecture as the harness sees it
(``cells.architecture``, named by a configuration's ``reference`` key):
besides the forward pass it hands on the seeded weights of this shape
and the program's layout of them (``benchmark/weights.py``) and says
which axes a weight's quantization scale is constant along.  A second
architecture is a second file with the same names.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights import (  # noqa: F401  (the architecture's interface)
    LAYER_LEAVES,
    TOP_LEAVES,
    dims_of,
    layer_key,
    layer_weights,
    program_layer,
    program_params,
    program_top,
    top_weights,
)

HI = jax.lax.Precision.HIGHEST

# contraction axes of each canonical leaf (weights.py): a weight's
# quantization scale is per output channel, constant along these.  The
# embedding is a table, not a product: its scale is per hidden column.
CONTRACT_AXES = {
    "wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
    "w_gate": (0,), "w_up": (0,), "w_down": (0,),
    "emb": (0,), "head": (0,),
}


def _id(x):
    return x


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, positions, theta):
    """x (B, S, H, D), positions (B, S); rotate-half."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal grouped-query attention; q (B,S,H,D), k/v (B,S,Hkv,D)."""
    b, s, h, d = q.shape
    g = h // k.shape[2]
    qg = q.reshape(b, s, k.shape[2], g, d)
    scores = jnp.einsum("bqhgd,bthd->bhgqt", qg, k, precision=HI)
    scores = scores / jnp.sqrt(jnp.float32(d))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqt,bthd->bqhgd", probs, v, precision=HI)
    return out.reshape(b, s, h, d)


def layer(x, w, positions, eps, theta, round_fn=_id, kv_fn=_id):
    r = round_fn
    h = r(rms_norm(x, w["attn_norm"], eps))
    q = jnp.einsum("bsd,dhk->bshk", h, r(w["wq"]), precision=HI)
    k = jnp.einsum("bsd,dhk->bshk", h, r(w["wk"]), precision=HI)
    v = jnp.einsum("bsd,dhk->bshk", h, r(w["wv"]), precision=HI)
    a = attention(r(rope(q, positions, theta)),
                  kv_fn(r(rope(k, positions, theta))), kv_fn(r(v)))
    x = x + jnp.einsum("bshk,hkd->bsd", r(a), r(w["wo"]), precision=HI)
    h = r(rms_norm(x, w["mlp_norm"], eps))
    gate = jnp.einsum("bsd,dm->bsm", h, r(w["w_gate"]), precision=HI)
    up = jnp.einsum("bsd,dm->bsm", h, r(w["w_up"]), precision=HI)
    act = r(jax.nn.silu(gate) * up)
    return x + jnp.einsum("bsm,md->bsd", act, r(w["w_down"]), precision=HI)


def embed(ids, emb):
    return jnp.take(emb, ids, axis=0)


def logits(x, top, eps, round_fn=_id):
    h = round_fn(rms_norm(x, top["final_norm"], eps))
    return jnp.einsum("...d,dv->...v", h, round_fn(top["head"]), precision=HI)


def next_token_loss(lg, ids):
    """Mean over rows of the mean next-token cross-entropy (S-1 targets)."""
    lp = jax.nn.log_softmax(lg[:, :-1], axis=-1)
    tok = jnp.take_along_axis(lp, ids[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(jnp.mean(tok, axis=-1))
