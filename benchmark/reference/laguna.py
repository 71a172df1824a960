"""Laguna-S-2.1's decoder (poolside; config.json as published) in plain
``jax.numpy``: float32, every product at ``highest`` precision, no
kernel, no cache, no sort.  Written from the equations, not from the
program; it imports nothing of ``mlcomp_tpu``.

Layer ``l`` has ``heads[l]`` query heads (48 in full-attention layers,
72 in sliding ones), 8 KV heads of 128, no biases:

- ``h = RMSNorm(x)``; ``q, k, v = h Wq, h Wk, h Wv``;
- RoPE by kind.  Sliding: plain, base 1e4, all 128 dimensions.  Full:
  YaRN on the first ``d_r = 64`` dimensions of each head, the other 64
  pass through: ``f_j = base^(-2j/d_r)``; ``dim(n) = d_r ln(orig / (2 pi
  n)) / (2 ln base)``; ``lo = max(floor(dim(beta_fast)), 0)``, ``hi =
  min(ceil(dim(beta_slow)), d_r - 1)``; ``ramp_j = clip((j - lo) / (hi -
  lo), 0, 1)``; ``inv_j = (f_j / factor) ramp_j + f_j (1 - ramp_j)``; cos
  and sin of ``pos inv_j`` both times ``attention_factor``;
- causal attention at ``1/sqrt(128)``; a sliding layer's query ``t``
  sees keys ``t - window + 1 .. t``;
- per-head output gate: ``g = sigmoid(h Wg)`` (one number a head),
  ``x += concat_h(g_h a_h) Wo``;
- MLP on ``u = RMSNorm(x)``: layer 0 a dense SwiGLU; the others a
  float32 router over all 256 experts, softmax, the top 10 renormalised
  to sum 1 and scaled by 2.5, SwiGLU experts, plus one shared SwiGLU
  expert on every token.

This chip's share (``dims_of``): the experts ``held = (first, count)``
and a slice of the vocabulary.  A token's assignments to experts not
held add nothing, here as in the program; the partial result goes on.

``assumed`` (the configuration file lists them): the gate is the
logistic function of the normed layer input; the router scores by
softmax; no q/k norm; the shared expert is not gated; RoPE pairs
dimension ``j`` with ``j + d_r/2``.

The names below are the ones ``benchmark/README.md`` asks of an
architecture ("A model with layers of several kinds").
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from benchmark import weights as W

HI = jax.lax.Precision.HIGHEST

TOP_LEAVES = ("emb", "final_norm", "head")
ATTN_LEAVES = ("attn_norm", "wq", "wk", "wv", "wg", "wo", "mlp_norm")
DENSE_LEAVES = ("w_gate", "w_up", "w_down")
SPARSE_LEAVES = ("router", "experts_gate", "experts_up", "experts_down",
                 "shared_gate", "shared_up", "shared_down")

# contraction axes (a weight's quantization scale is constant along
# them).  Stacked experts are (expert, in, out).  The router is float32
# in the program whatever the weights are: no entry, never rounded.
CONTRACT_AXES = {
    "wq": (0,), "wk": (0,), "wv": (0,), "wg": (0,), "wo": (0, 1),
    "w_gate": (0,), "w_up": (0,), "w_down": (0,),
    "experts_gate": (1,), "experts_up": (1,), "experts_down": (1,),
    "shared_gate": (0,), "shared_up": (0,), "shared_down": (0,),
    "emb": (0,), "head": (0,),
}


def _rope(spec: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "base": float(spec["rope_theta"]),
        "share": float(spec.get("partial_rotary_factor", 1.0)),
        "yarn": spec.get("rope_type") == "yarn",
        "factor": float(spec.get("factor", 1.0)),
        "original_max": float(spec.get("original_max_position_embeddings", 0)),
        "beta_fast": float(spec.get("beta_fast", 32)),
        "beta_slow": float(spec.get("beta_slow", 1)),
        "attention_factor": float(spec.get("attention_factor", 1.0)),
    }


def dims_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Everything the functions below need, from a configuration file:
    the published sizes, the per-layer lists cut to the layers held, and
    this chip's share.  No function here reads the configuration again."""
    n = int(cfg["num_hidden_layers"])
    share = cfg["share"]
    return {
        "vocab": int(cfg["vocab_size"]),
        "hidden": int(cfg["hidden_size"]),
        "layers": n,
        "heads": [int(h) for h in cfg["num_attention_heads_per_layer"][:n]],
        "attn": [t.split("_")[0] for t in cfg["layer_types"][:n]],
        "mlp_kind": list(cfg["mlp_layer_types"][:n]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["head_dim"]),
        "mlp": int(cfg["intermediate_size"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "window": int(cfg["sliding_window"]),
        "rope": {
            kind.split("_")[0]: _rope(spec)
            for kind, spec in cfg["rope_parameters"].items()
        },
        "experts": int(share["experts_published"]),
        "held": (int(share["experts_first"]), int(cfg["num_experts"])),
        "top_k": int(cfg["num_experts_per_tok"]),
        "routed_scale": float(cfg["moe_routed_scaling_factor"]),
        "expert_width": int(cfg["moe_intermediate_size"]),
        "shared_width": int(cfg["shared_expert_intermediate_size"]),
    }


def layer_kinds(d: Dict[str, Any]) -> List[str]:
    """``<mlp kind>_<attention kind>`` a layer; layers of one name have
    the same leaves of the same shapes."""
    return [f"{m}_{a}" for m, a in zip(d["mlp_kind"], d["attn"])]


def _heads_of(kind: str, d: Dict[str, Any]) -> int:
    return d["heads"][layer_kinds(d).index(kind)]


def _shapes(d: Dict[str, Any], kind: str) -> W.Shapes:
    """(shape, fan_in) of every leaf of a layer of ``kind``."""
    h, dh, hkv = d["hidden"], d["head_dim"], d["kv_heads"]
    nh = _heads_of(kind, d)
    shapes: W.Shapes = {
        "attn_norm": ((h,), None),
        "wq": ((h, nh, dh), h),
        "wk": ((h, hkv, dh), h),
        "wv": ((h, hkv, dh), h),
        "wg": ((h, nh), h),
        "wo": ((nh, dh, h), nh * dh),
        "mlp_norm": ((h,), None),
    }
    if kind.startswith("dense"):
        f = d["mlp"]
        shapes.update({"w_gate": ((h, f), h), "w_up": ((h, f), h),
                       "w_down": ((f, h), f)})
    else:
        e, f, s = d["held"][1], d["expert_width"], d["shared_width"]
        shapes.update({
            "router": ((h, d["experts"]), h),
            "experts_gate": ((e, h, f), h),
            "experts_up": ((e, h, f), h),
            "experts_down": ((e, f, h), f),
            "shared_gate": ((h, s), h),
            "shared_up": ((h, s), h),
            "shared_down": ((s, h), s),
        })
    return shapes


def layer_weights(key, i, d, dtype, kind) -> Dict[str, Any]:
    return W.layer_leaves(key, i, _shapes(d, kind), dtype)


def top_weights(key, d, dtype) -> Dict[str, Any]:
    h, v = d["hidden"], d["vocab"]
    return W.top_leaves(key, {
        "emb": ((v, h), h), "final_norm": ((h,), None), "head": ((h, v), h),
    }, dtype)


def layer_key(i: int, d: Dict[str, Any]) -> str:
    return f"layer_{i}"


def program_layer(w: Dict[str, Any], kind: str) -> Dict[str, Any]:
    """One layer in the parameter layout of ``mixed_layer_lm``."""
    out = {
        "attn": {
            "RMSNorm_0": {"scale": w["attn_norm"]},
            "q": {"kernel": w["wq"]},
            "k": {"kernel": w["wk"]},
            "v": {"kernel": w["wv"]},
            "head_gate": {"kernel": w["wg"]},
            "out": {"kernel": w["wo"]},
        },
        "RMSNorm_0": {"scale": w["mlp_norm"]},
    }
    if kind.startswith("dense"):
        out.update({"gate": {"kernel": w["w_gate"]},
                    "up": {"kernel": w["w_up"]},
                    "down": {"kernel": w["w_down"]}})
    else:
        out["moe"] = {
            "router": {"kernel": w["router"]},
            "experts_gate": w["experts_gate"],
            "experts_up": w["experts_up"],
            "experts_down": w["experts_down"],
            "shared_gate": {"kernel": w["shared_gate"]},
            "shared_up": {"kernel": w["shared_up"]},
            "shared_down": {"kernel": w["shared_down"]},
        }
    return out


def program_top(top: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "emb": {"embedding": top["emb"]},
        "RMSNorm_0": {"scale": top["final_norm"]},
        "lm_head": {"kernel": top["head"]},
    }


def rows_per_block(d: Dict[str, Any], pad_len: int) -> int:
    """Rows the serve comparison puts through a layer together: the
    float32 scores of one row are heads x pad_len^2 x 4 B (302 MB at 72
    heads and 1,024 tokens), beside a sparse layer's float32 experts
    (4.8 GB, and as much again for the control's)."""
    per_row = max(d["heads"]) * pad_len * pad_len * 4
    return max(1, min(2, int(0.5e9 // per_row)))


def _id(x):
    return x


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope_angles(r: Dict[str, Any], head_dim: int):
    """(rotary width d_r, its d_r / 2 angular frequencies)."""
    d_r = int(round(r["share"] * head_dim))
    j = jnp.arange(d_r // 2, dtype=jnp.float32)
    f = r["base"] ** (-2.0 * j / d_r)
    if not r["yarn"]:
        return d_r, f

    def dim(n):
        return d_r * math.log(r["original_max"] / (2.0 * math.pi * n)) / (
            2.0 * math.log(r["base"])
        )

    lo = max(math.floor(dim(r["beta_fast"])), 0)
    hi = min(math.ceil(dim(r["beta_slow"])), d_r - 1)
    ramp = jnp.clip((j - lo) / (hi - lo), 0.0, 1.0)
    return d_r, (f / r["factor"]) * ramp + f * (1.0 - ramp)


def rope(x, positions, r: Dict[str, Any]):
    """x (B, S, H, D), positions (B, S); dimension j of the rotating
    part pairs with j + d_r / 2; the rest passes through."""
    d_r, inv = rope_angles(r, x.shape[-1])
    ang = positions[..., None].astype(jnp.float32) * inv
    cos = (jnp.cos(ang) * r["attention_factor"])[:, :, None, :]
    sin = (jnp.sin(ang) * r["attention_factor"])[:, :, None, :]
    x1, x2, rest = x[..., :d_r // 2], x[..., d_r // 2:d_r], x[..., d_r:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1
    )


def attention(q, k, v, window=None):
    """Causal grouped-query attention; q (B,S,H,D), k/v (B,S,Hkv,D);
    with ``window`` query t sees keys t - window + 1 .. t."""
    b, s, h, dh = q.shape
    g = h // k.shape[2]
    qg = q.reshape(b, s, k.shape[2], g, dh)
    scores = jnp.einsum("bqhgd,bthd->bhgqt", qg, k, precision=HI)
    scores = scores / jnp.sqrt(jnp.float32(dh))
    t_q, t_k = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = t_q >= t_k
    if window is not None:
        seen = seen & (t_k > t_q - window)
    scores = jnp.where(seen[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqt,bthd->bqhgd", probs, v, precision=HI)
    return out.reshape(b, s, h, dh)


def swiglu(h, w_gate, w_up, w_down):
    gate = jnp.einsum("...d,df->...f", h, w_gate, precision=HI)
    up = jnp.einsum("...d,df->...f", h, w_up, precision=HI)
    return jnp.einsum("...f,fd->...d", jax.nn.silu(gate) * up, w_down,
                      precision=HI)


def route(u, router, d):
    """(B, S, experts) weight of every published expert for each token:
    the top ``top_k`` of the softmax, renormalised, scaled; 0 elsewhere."""
    probs = jax.nn.softmax(
        jnp.einsum("bsd,de->bse", u, router, precision=HI), axis=-1)
    top, idx = jax.lax.top_k(probs, d["top_k"])
    gates = top / jnp.sum(top, axis=-1, keepdims=True) * d["routed_scale"]
    return jnp.sum(
        jax.nn.one_hot(idx, d["experts"], dtype=jnp.float32)
        * gates[..., None], axis=-2)


def routed(u, w, d, held=None):
    """The held experts' part of the routed sum: a plain loop over the
    experts held, every token through each, weighted by ``route``."""
    first, count = d["held"] if held is None else held
    weight = route(u, w["router"], d)

    def one(e, acc):
        out = swiglu(u, w["experts_gate"][e], w["experts_up"][e],
                     w["experts_down"][e])
        m = jax.lax.dynamic_index_in_dim(weight, first + e, 2, keepdims=True)
        return acc + m * out

    return jax.lax.fori_loop(0, count, one, jnp.zeros_like(u))


def layer(x, w, positions, d, kind, round_fn=_id, kv_fn=_id):
    """One layer of ``kind``.  ``round_fn`` (training's control) is not
    applied: this architecture is only served."""
    mlp_kind, attn_kind = kind.split("_")
    eps, r = d["norm_eps"], d["rope"][attn_kind]
    h = rms_norm(x, w["attn_norm"], eps)
    q = jnp.einsum("bsd,dhk->bshk", h, w["wq"], precision=HI)
    k = jnp.einsum("bsd,dhk->bshk", h, w["wk"], precision=HI)
    v = jnp.einsum("bsd,dhk->bshk", h, w["wv"], precision=HI)
    a = attention(
        rope(q, positions, r), kv_fn(rope(k, positions, r)), kv_fn(v),
        window=d["window"] if attn_kind == "sliding" else None,
    )
    g = jax.nn.sigmoid(jnp.einsum("bsd,dh->bsh", h, w["wg"], precision=HI))
    x = x + jnp.einsum("bshk,hkd->bsd", a * g[..., None], w["wo"],
                       precision=HI)
    u = rms_norm(x, w["mlp_norm"], eps)
    if mlp_kind == "dense":
        return x + swiglu(u, w["w_gate"], w["w_up"], w["w_down"])
    return x + routed(u, w, d) + swiglu(
        u, w["shared_gate"], w["shared_up"], w["shared_down"])


def embed(ids, emb):
    return jnp.take(emb, ids, axis=0)


def logits(x, top, d, round_fn=_id):
    h = rms_norm(x, top["final_norm"], d["norm_eps"])
    return jnp.einsum("...d,dv->...v", h, top["head"], precision=HI)
