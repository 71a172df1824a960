"""LFM2-24B-A2B's decoder (LiquidAI; config.json as published,
``model_type`` ``lfm2_moe``; the layers as the family's public
``modeling_lfm2_moe.py`` computes them) in plain ``jax.numpy``: float32,
every product at ``highest`` precision, no kernel, no cache, no sort.
Written from the equations, not from the program; it imports nothing of
``mlcomp_tpu``.

Layer ``i`` (0-indexed, as ``layer_types`` counts) is a gated short
convolution (``"conv"``) or grouped-query attention
(``"full_attention"``); the first ``num_dense_layers`` layers' FFN is a
dense SwiGLU, the others' routed experts.  No bias anywhere.  With
pre-norm residuals, ``x <- x + Mixer_i(RMSNorm(x))``, then ``x <- x +
FFN_i(RMSNorm(x))``; after the last layer one RMSNorm, then the head.

**Short convolution** (``conv_L_cache`` 3 taps, ``conv_bias`` false).
With ``h`` the normed input:

- ``[B | C | X] = h W_in`` (hidden -> 3 x hidden, in that order);
- ``u = B * X``;
- ``c_t = sum_{j=0..2} w_j * u_{t-2+j}``: depthwise (one 3-vector a
  channel), causal, zeros before the first token, no bias, NO
  activation after it;
- ``y = (C * c) W_out``.

**Attention** (32 heads over 8 KV heads of 64).  ``q, k, v = h W_q, h
W_k, h W_v``; ``q`` and ``k`` are RMS-normed a head over its 64 channels
with a learned 64-vector each (``q_layernorm``, ``k_layernorm``) BEFORE
the rotation; RoPE at ``rope_theta`` 1e6 over the whole head, dimension
``j`` paired with ``j + 32``; causal softmax at ``64^-1/2``, four query
heads a KV head; ``y = a W_out``.

**FFN**.  Dense: SwiGLU of ``intermediate_size``.  Routed: ``s =
sigmoid(x W_r)`` over ``num_experts`` (float32); the top
``num_experts_per_tok`` by ``s + expert_bias`` (``use_expert_bias``: the
bias joins the CHOICE alone), weighted by ``s`` itself at the chosen,
``w / (sum w + 1e-6)`` (``norm_topk_prob``), times
``routed_scaling_factor``; each expert a SwiGLU of
``moe_intermediate_size``; no shared expert.

``assumed`` (the configuration file gives the reasoning of each): an
untied head, ``expert_bias`` drawn small, the two head norms' scales
drawn around 2 (scores of deviation ~4: the softmax picks tokens, and
the keys' and values' precision shows in the logits), a float32 router.

The names below are the ones ``benchmark/README.md`` asks of an
architecture ("A model with layers of several kinds").  The attention
runs in blocks of queries: at 3,585 positions one row's float32 scores
are 32 x 3,585^2 x 4 B = 1.6 GB whole.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from benchmark import weights as W

HI = jax.lax.Precision.HIGHEST
# queries a block of the attention: 32 heads x 256 x 3,585 keys x 4 B =
# 117 MB of scores at the cell's pad length
Q_BLOCK = 256
# the router's selection bias is drawn N(0, 1 / this): ~0.02, against
# scores that differ by ~0.1 from one expert to the next, so that it
# changes some choices and not all
BIAS_FAN_IN = 2500
# the two head norms' scales are HEAD_NORM_GAIN x (1 + N(0, 1 / this)):
# ~0.25 about 1, times the gain.  At all ones a q or k of unit variance
# would pass through its norm nearly unchanged, and a program without
# the norm would agree
NORM_FAN_IN = 16
# A normed q . k over 64 channels / 8 has deviation g_q g_k.  At 1 the
# softmax over 1,000-3,500 seeded keys is nearly flat: the layer's
# output is a mean of values, 0.05 of a conv layer's at 2,048 keys, and
# int4 keys and values move the logits LESS than the served path's
# bfloat16 arithmetic does (PERF.md section 6, PR 43).  A trained
# attention layer picks tokens; at a gain of 2 each the scores'
# deviation is 4, a query's best key of 2,000 stands ~14 above the mean
# (ln 2,000 = 7.6), and the output is half a conv layer's
HEAD_NORM_GAIN = 2.0
RENORM_EPS = 1e-6

TOP_LEAVES = ("emb", "final_norm", "head")

# contraction axes (a weight's quantization scale is constant along
# them).  Stacked experts are (expert, in, out).  The router and its
# bias, the convolution's taps and the norms are float32 in the program
# whatever the weights are: no entry, never rounded.
CONTRACT_AXES = {
    "w_in": (0,), "w_out": (0,),
    "wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
    "w_gate": (0,), "w_up": (0,), "w_down": (0,),
    "experts_gate": (1,), "experts_up": (1,), "experts_down": (1,),
    "emb": (0,), "head": (0,),
}

KINDS = {"conv": "conv", "full_attention": "attn"}


def dims_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Everything the functions below need, from a configuration file:
    the published sizes and the layer pattern cut to the layers held
    (``share.first_layer`` on).  No function here reads the
    configuration again."""
    if cfg["conv_bias"] or not cfg["norm_topk_prob"] \
            or not cfg["use_expert_bias"] \
            or cfg["rope_parameters"]["rope_type"] != "default":
        raise NotImplementedError(
            "a convolution with a bias, weights not renormalised, no "
            "expert bias or a scaled RoPE: none is written down"
        )
    n = int(cfg["num_hidden_layers"])
    first = int(cfg["share"]["first_layer"])
    held = range(first, first + n)
    heads = int(cfg["num_attention_heads"])
    return {
        "vocab": int(cfg["vocab_size"]),
        "hidden": int(cfg["hidden_size"]),
        "layers": n,
        "attn": [KINDS[cfg["layer_types"][i]] for i in held],
        "dense": [i < int(cfg["num_dense_layers"]) for i in held],
        "heads": heads,
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["hidden_size"]) // heads,
        "taps": int(cfg["conv_L_cache"]),
        "mlp": int(cfg["intermediate_size"]),
        "norm_eps": float(cfg["as_run"]["norm_eps"]),
        "rope_theta": float(cfg["rope_parameters"]["rope_theta"]),
        "experts": int(cfg["num_experts"]),
        "top_k": int(cfg["num_experts_per_tok"]),
        "routed_scale": float(cfg["routed_scaling_factor"]),
        "expert_width": int(cfg["moe_intermediate_size"]),
    }


def layer_kinds(d: Dict[str, Any]) -> List[str]:
    """``"conv_dense"``, ``"conv_sparse"``, ``"attn_sparse"`` (and
    ``"attn_dense"``, which the published pattern lacks): the mixer,
    then the FFN; layers of one name have the same leaves of the same
    shapes."""
    return [a + ("_dense" if dense else "_sparse")
            for a, dense in zip(d["attn"], d["dense"])]


def _shapes(d: Dict[str, Any], kind: str) -> W.Shapes:
    """(shape, fan_in) of every drawn leaf of a layer of ``kind``."""
    h = d["hidden"]
    shapes: W.Shapes = {"attn_norm": ((h,), None)}
    if kind.startswith("conv"):
        shapes.update({
            "w_in": ((h, 3 * h), h),
            "conv": ((d["taps"], h), d["taps"]),
            "w_out": ((h, h), h),
        })
    else:
        nh, hkv, dh = d["heads"], d["kv_heads"], d["head_dim"]
        shapes.update({
            "wq": ((h, nh, dh), h), "wk": ((h, hkv, dh), h),
            "wv": ((h, hkv, dh), h),
            "q_norm": ((dh,), NORM_FAN_IN), "k_norm": ((dh,), NORM_FAN_IN),
            "wo": ((nh, dh, h), nh * dh),
        })
    shapes["mlp_norm"] = ((h,), None)
    if kind.endswith("dense"):
        f = d["mlp"]
        shapes.update({"w_gate": ((h, f), h), "w_up": ((h, f), h),
                       "w_down": ((f, h), f)})
    else:
        e, f = d["experts"], d["expert_width"]
        shapes.update({
            "router": ((h, e), h),
            "router_bias": ((e,), BIAS_FAN_IN),
            "experts_gate": ((e, h, f), h),
            "experts_up": ((e, h, f), h),
            "experts_down": ((e, f, h), f),
        })
    return shapes


def layer_weights(key, i, d, dtype, kind) -> Dict[str, Any]:
    w = W.layer_leaves(key, i, _shapes(d, kind), dtype)
    for name in ("q_norm", "k_norm"):
        if name in w:
            # drawn around the gain, float32 (a norm's scale is never
            # rounded)
            w[name] = HEAD_NORM_GAIN * (1.0 + w[name].astype(jnp.float32))
    return w


def top_weights(key, d, dtype) -> Dict[str, Any]:
    h, v = d["hidden"], d["vocab"]
    return W.top_leaves(key, {
        "emb": ((v, h), h), "final_norm": ((h,), None), "head": ((h, v), h),
    }, dtype)


def layer_key(i: int, d: Dict[str, Any]) -> str:
    return f"layer_{i}"


def program_layer(w: Dict[str, Any], kind: str) -> Dict[str, Any]:
    """One layer in the parameter layout of ``mixed_layer_lm``."""
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    if kind.startswith("conv"):
        attn = {"in": {"kernel": w["w_in"]}, "conv": f32(w["conv"]),
                "out": {"kernel": w["w_out"]}}
    else:
        attn = {
            "q": {"kernel": w["wq"]}, "k": {"kernel": w["wk"]},
            "v": {"kernel": w["wv"]},
            "q_norm": w["q_norm"], "k_norm": w["k_norm"],
            "out": {"kernel": w["wo"]},
        }
    out = {
        "attn": {"RMSNorm_0": {"scale": w["attn_norm"]}, **attn},
        "RMSNorm_0": {"scale": w["mlp_norm"]},
    }
    if kind.endswith("dense"):
        out.update({"gate": {"kernel": w["w_gate"]},
                    "up": {"kernel": w["w_up"]},
                    "down": {"kernel": w["w_down"]}})
    else:
        out["moe"] = {
            "router": {"kernel": w["router"]},
            "router_bias": f32(w["router_bias"]),
            "experts_gate": w["experts_gate"],
            "experts_up": w["experts_up"],
            "experts_down": w["experts_down"],
        }
    return out


def program_top(top: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "emb": {"embedding": top["emb"]},
        "RMSNorm_0": {"scale": top["final_norm"]},
        "lm_head": {"kernel": top["head"]},
    }


def rows_per_block(d: Dict[str, Any], pad_len: int) -> int:
    """Rows the serve comparison puts through a layer together: what a
    query block's float32 scores (heads x Q_BLOCK x pad_len x 4 B a
    row) leave of half a gigabyte, beside a sparse layer's float32
    weights (2.47 GB, and as much again for each control's)."""
    per_row = d["heads"] * min(Q_BLOCK, pad_len) * pad_len * 4
    return max(1, min(2, int(0.5e9 // per_row)))


def _id(x):
    return x


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def causal_conv(u, taps):
    """``u`` (B, S, C), ``taps`` (T, C): ``c_t = sum_j taps[j] u_{t - T
    + 1 + j}``, zeros before the first token."""
    t, s = taps.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (t - 1, 0), (0, 0)))
    return sum(taps[j] * padded[:, j:j + s] for j in range(t))


def after_conv(c):
    """What follows the convolution before the gate: nothing."""
    return c


def out_gate(gate, c):
    """The convolution's output under its gate ``C``."""
    return gate * c


def short_conv(x, w, d):
    h = rms_norm(x, w["attn_norm"], d["norm_eps"])
    bcx = jnp.einsum("bsd,dc->bsc", h, w["w_in"], precision=HI)
    gate_in, gate, value = jnp.split(bcx, 3, axis=-1)
    c = after_conv(causal_conv(gate_in * value, w["conv"]))
    return x + jnp.einsum("bsc,cd->bsd", out_gate(gate, c), w["w_out"],
                          precision=HI)


def head_norm(x, scale, eps):
    """``q_layernorm`` / ``k_layernorm``: RMSNorm over a head's
    channels, a learned vector a head width."""
    return rms_norm(x, scale, eps)


def rope(x, positions, theta: float):
    """x (B, S, H, D), positions (B, S): the whole head rotates,
    dimension j paired with j + D / 2."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal grouped-query attention; q (B,S,H,D), k/v (B,S,Hkv,D): a
    block of Q_BLOCK queries at a time against every key."""
    b, s, h, dh = q.shape
    hkv = k.shape[2]
    blk = min(Q_BLOCK, s)
    n_blk = -(-s // blk)
    qp = jnp.pad(q, ((0, 0), (0, n_blk * blk - s), (0, 0), (0, 0)))
    qp = qp.reshape(b, n_blk, blk, hkv, h // hkv, dh).transpose(1, 0, 2, 3, 4, 5)
    t_k = jnp.arange(s)[None, :]

    def one(args):
        qb, first = args
        # a pad query past the end stands where the last real one does
        t_q = jnp.minimum(first + jnp.arange(blk), s - 1)[:, None]
        scores = jnp.einsum("bqhgd,bthd->bhgqt", qb, k, precision=HI)
        scores = scores / jnp.sqrt(jnp.float32(dh))
        scores = jnp.where((t_q >= t_k)[None, None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhgqt,bthd->bqhgd", probs, v, precision=HI)

    out = jax.lax.map(one, (qp, jnp.arange(n_blk) * blk))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, n_blk * blk, h, dh)
    return out[:, :s]


def gqa(x, w, positions, d, kv_fn=_id):
    eps = d["norm_eps"]
    h = rms_norm(x, w["attn_norm"], eps)
    q = jnp.einsum("bsd,dhk->bshk", h, w["wq"], precision=HI)
    k = jnp.einsum("bsd,dhk->bshk", h, w["wk"], precision=HI)
    v = jnp.einsum("bsd,dhk->bshk", h, w["wv"], precision=HI)
    q = rope(head_norm(q, w["q_norm"], eps), positions, d["rope_theta"])
    k = rope(head_norm(k, w["k_norm"], eps), positions, d["rope_theta"])
    # what the cache holds: the normed, rotated keys and the values
    a = attention(q, kv_fn(k), kv_fn(v))
    return x + jnp.einsum("bshk,hkd->bsd", a, w["wo"], precision=HI)


def swiglu(u, w_gate, w_up, w_down):
    gate = jnp.einsum("...d,df->...f", u, w_gate, precision=HI)
    up = jnp.einsum("...d,df->...f", u, w_up, precision=HI)
    return jnp.einsum("...f,fd->...d", jax.nn.silu(gate) * up, w_down,
                      precision=HI)


def chosen(s, bias, k):
    """The experts a token takes: the top ``k`` of score + bias."""
    return jax.lax.top_k(s + bias, k)[1]


def route(u, w, d):
    """(B, S, experts) weight of every expert for each token: the
    scores of the chosen, ``w / (sum w + 1e-6)``, scaled; 0 elsewhere."""
    s = jax.nn.sigmoid(
        jnp.einsum("bsd,de->bse", u, w["router"], precision=HI))
    idx = chosen(s, w["router_bias"], d["top_k"])
    picked = s * jax.nn.one_hot(idx, d["experts"], dtype=jnp.float32).sum(-2)
    total = jnp.sum(picked, axis=-1, keepdims=True) + RENORM_EPS
    return picked / total * d["routed_scale"]


def routed(u, w, d):
    """The routed sum: a plain loop over the experts, every token
    through each, weighted by ``route``."""
    weight = route(u, w, d)

    def one(e, acc):
        out = swiglu(u, w["experts_gate"][e], w["experts_up"][e],
                     w["experts_down"][e])
        m = jax.lax.dynamic_index_in_dim(weight, e, 2, keepdims=True)
        return acc + m * out

    return jax.lax.fori_loop(0, d["experts"], one, jnp.zeros_like(u))


def layer(x, w, positions, d, kind, round_fn=_id, kv_fn=_id):
    """One layer of ``kind``.  ``round_fn`` (training's control) is not
    applied: this architecture is only served; ``kv_fn`` rounds the
    keys and values an attention layer reads (a conv layer has none:
    its tail is kept as it is)."""
    if kind.startswith("conv"):
        x = short_conv(x, w, d)
    else:
        x = gqa(x, w, positions, d, kv_fn)
    u = rms_norm(x, w["mlp_norm"], d["norm_eps"])
    if kind.endswith("dense"):
        return x + swiglu(u, w["w_gate"], w["w_up"], w["w_down"])
    return x + routed(u, w, d)


def embed(ids, emb):
    return jnp.take(emb, ids, axis=0)


def logits(x, top, d, round_fn=_id):
    h = rms_norm(x, top["final_norm"], d["norm_eps"])
    return jnp.einsum("...d,dv->...v", h, top["head"], precision=HI)
