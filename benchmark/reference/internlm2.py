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
the RoPE base and the norm's epsilon are read from the configuration's
``as_run`` (``dims_of``), the program's 10000 and 1e-6 where InternLM2
publishes 1e6 and 1e-5.  InternLM2 stores q, k and v packed in one
``wqkv``; they are separate leaves here — a layout, not arithmetic.

``round_fn`` rounds every product's operands (identity for the
reference itself); the training control passes a float8 rounding.
``kv_fn`` rounds the keys and values attention reads (identity for the
reference; serving's second control passes an int4 rounding).

This file is also the architecture as the harness sees it
(``cells.architecture``, named by a configuration's ``reference`` key;
``benchmark/README.md`` lists the names and who calls each): the sizes
(``dims_of``), the list of layer kinds (``layer_kinds``: every layer of
InternLM2 is one kind), the shapes of the seeded leaves
(``layer_weights``, ``top_weights``; ``benchmark/weights.py`` draws
them), the program's layout of them (``layer_key``, ``program_layer``,
``program_top``), which axes a weight's quantization scale is constant
along (``CONTRACT_AXES``) and how many rows the comparison can put
through a layer together (``rows_per_block``).  A second architecture is
a second file with the same names.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from benchmark import weights as W

HI = jax.lax.Precision.HIGHEST

KIND = "decoder"
# canonical order of the leaves: training's comparison lists its numbers
# in it, and a leaf's seeded values follow from its place in it
LAYER_LEAVES = (
    "attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate", "w_up",
    "w_down",
)
TOP_LEAVES = ("emb", "final_norm", "head")

# contraction axes of each canonical leaf (weights.py): a weight's
# quantization scale is per output channel, constant along these.  The
# embedding is a table, not a product: its scale is per hidden column.
CONTRACT_AXES = {
    "wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
    "w_gate": (0,), "w_up": (0,), "w_down": (0,),
    "emb": (0,), "head": (0,),
}


def dims_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Everything the functions below need, from a configuration file:
    the published sizes and the two constants the program fixes in code
    (``as_run``).  No function here reads the configuration again."""
    heads = int(cfg["num_attention_heads"])
    hidden = int(cfg["hidden_size"])
    return {
        "vocab": int(cfg["vocab_size"]),
        "hidden": hidden,
        "layers": int(cfg["num_hidden_layers"]),
        "heads": heads,
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": hidden // heads,
        "mlp": int(cfg["intermediate_size"]),
        "norm_eps": float(cfg["as_run"]["norm_eps"]),
        "rope_base": float(cfg["as_run"]["rope_base"]),
    }


def layer_kinds(d: Dict[str, Any]) -> List[str]:
    """One name a layer, in order; a program is compiled per name."""
    return [KIND] * d["layers"]


def _shapes(d: Dict[str, Any]) -> W.Shapes:
    """(shape, fan_in) of every leaf; fan_in None = a norm scale.
    Projections are (hidden, heads, head_dim) / (heads, head_dim,
    hidden), MLP matrices (in, out), embedding (vocab, hidden), head
    (hidden, vocab)."""
    h, dh = d["hidden"], d["head_dim"]
    return {
        "attn_norm": ((h,), None),
        "mlp_norm": ((h,), None),
        "final_norm": ((h,), None),
        "wq": ((h, d["heads"], dh), h),
        "wk": ((h, d["kv_heads"], dh), h),
        "wv": ((h, d["kv_heads"], dh), h),
        "wo": ((d["heads"], dh, h), d["heads"] * dh),
        "w_gate": ((h, d["mlp"]), h),
        "w_up": ((h, d["mlp"]), h),
        "w_down": ((d["mlp"], h), d["mlp"]),
        "emb": ((d["vocab"], h), h),
        "head": ((h, d["vocab"]), h),
    }


def layer_weights(key, i, d, dtype, kind=KIND) -> Dict[str, Any]:
    """Layer ``i``'s leaves (``i`` may be a traced integer); shapes
    follow from the kind alone."""
    shapes = _shapes(d)
    return W.layer_leaves(key, i, {n: shapes[n] for n in LAYER_LEAVES}, dtype)


def top_weights(key, d, dtype) -> Dict[str, Any]:
    shapes = _shapes(d)
    return W.top_leaves(key, {n: shapes[n] for n in TOP_LEAVES}, dtype)


def layer_key(i: int, d: Dict[str, Any]) -> str:
    """Where layer ``i`` sits in the program's parameter tree."""
    return f"DecoderLayer_{i}"


def program_layer(w: Dict[str, Any], kind=KIND) -> Dict[str, Any]:
    """One layer in the parameter layout of ``TransformerLM``."""
    return {
        "attn": {
            "RMSNorm_0": {"scale": w["attn_norm"]},
            "q": {"kernel": w["wq"]},
            "k": {"kernel": w["wk"]},
            "v": {"kernel": w["wv"]},
            "out": {"kernel": w["wo"]},
        },
        "RMSNorm_0": {"scale": w["mlp_norm"]},
        "gate": {"kernel": w["w_gate"]},
        "up": {"kernel": w["w_up"]},
        "down": {"kernel": w["w_down"]},
    }


def program_top(top: Dict[str, Any]) -> Dict[str, Any]:
    """The leaves outside the layers, in ``TransformerLM``'s layout."""
    return {
        "emb": {"embedding": top["emb"]},
        "RMSNorm_0": {"scale": top["final_norm"]},
        "lm_head": {"kernel": top["head"]},
    }


def rows_per_block(d: Dict[str, Any], pad_len: int) -> int:
    """Rows the serve comparison puts through a layer together.  The
    float32 scores of one row are heads x pad_len^2 x 4 B (340 MB at
    2304 tokens); two rows fit the chip beside the reference's weights,
    and a block is never more than two."""
    per_row = d["heads"] * pad_len * pad_len * 4
    return max(1, min(2, int(1.0e9 // per_row)))


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


def layer(x, w, positions, d, kind=KIND, round_fn=_id, kv_fn=_id):
    r = round_fn
    eps, theta = d["norm_eps"], d["rope_base"]
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


def logits(x, top, d, round_fn=_id):
    h = round_fn(rms_norm(x, top["final_norm"], d["norm_eps"]))
    return jnp.einsum("...d,dv->...v", h, round_fn(top["head"]), precision=HI)

