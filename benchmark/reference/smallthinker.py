"""SmallThinker-21BA3B-Instruct's decoder (PowerInfer; config.json as
published) in plain ``jax.numpy``: float32, every product at ``highest``
precision, no kernel, no cache, no sort.  Written from the equations,
not from the program; it imports nothing of ``mlcomp_tpu``.

Every layer has 28 query heads over 4 KV heads of 128, no biases, and 64
routed experts of width 768 of which a token takes 6; no dense MLP, no
shared expert.  Layer ``i`` (config keys in brackets):

- ``h = RMSNorm(x)`` (``rms_norm_eps``); ``q, k, v = h Wq, h Wk, h Wv``;
- where ``rope_layout[i]`` is 1 (all but every fourth layer) ``q`` and
  ``k`` rotate: plain RoPE at ``rope_theta`` 1.5e6 over the whole head,
  no scaling (``rope_scaling`` null), and query ``t`` sees keys
  ``t - 4095 .. t`` (``sliding_window_layout[i]`` 1,
  ``sliding_window_size`` 4096).  Where both are 0 (layers 0, 4, 8, ...)
  NOTHING rotates (the rotation is not called) and a query sees every
  earlier key.  Softmax at ``1/sqrt(128)``; ``x += a Wo``;
- ``r = h W_router``: the router reads ``h``, the layer's PRE-attention
  normed input, in float32.  The top 6 by logit
  (``moe_num_active_primary_experts``) weigh by the softmax over those
  six logits (``moe_primary_router_apply_softmax``; they already sum to
  1, so ``norm_topk_prob`` changes nothing);
- ``u = RMSNorm(x)`` (post-attention); ``x += sum_e w_e W_down,e
  (relu(W_gate,e u) * W_up,e u)``: ReGLU experts of width
  ``moe_ffn_hidden_size``;
- after the last layer a final RMSNorm and an untied head.

Departures from the published description: none.

``assumed`` (the configuration file lists them with the phrase of the
catalog's ``described_as`` each rests on): the router before the
attention, the ReLU gate, top-k before the softmax, no secondary
experts, RoPE pairing dimension ``j`` with ``j + 64``.

The names below are the ones ``benchmark/README.md`` asks of an
architecture ("A model with layers of several kinds").  The attention
runs in blocks of queries: at 12,800 positions one row's float32 scores
are 28 x 12,800^2 x 4 B = 18 GB whole.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from benchmark import weights as W

HI = jax.lax.Precision.HIGHEST
# queries a block of the attention: 28 heads x 256 x 12,800 keys x 4 B
# = 367 MB of scores at the cell's pad length
Q_BLOCK = 256

TOP_LEAVES = ("emb", "final_norm", "head")

# contraction axes (a weight's quantization scale is constant along
# them).  Stacked experts are (expert, in, out).  The router is float32
# in the program whatever the weights are: no entry, never rounded.
CONTRACT_AXES = {
    "wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
    "experts_gate": (1,), "experts_up": (1,), "experts_down": (1,),
    "emb": (0,), "head": (0,),
}


def dims_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Everything the functions below need, from a configuration file:
    the published sizes and the two per-layer lists cut to the layers
    held.  No function here reads the configuration again."""
    n = int(cfg["num_hidden_layers"])
    sliding = [int(v) for v in cfg["sliding_window_layout"][:n]]
    rotates = [int(v) for v in cfg["rope_layout"][:n]]
    if sliding != rotates:
        raise NotImplementedError(
            "a layer kind is named by its window alone: rope_layout "
            f"{rotates} would have to equal sliding_window_layout {sliding}"
        )
    if cfg.get("rope_scaling") is not None:
        raise NotImplementedError("rope_scaling: only null is written down")
    return {
        "vocab": int(cfg["vocab_size"]),
        "hidden": int(cfg["hidden_size"]),
        "layers": n,
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["head_dim"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "attn": ["sliding" if s else "full" for s in sliding],
        # which kinds rotate q and k, and which see a window of keys
        "rotates": {"sliding": True, "full": False},
        "window": {"sliding": int(cfg["sliding_window_size"]), "full": None},
        "rope_theta": float(cfg["rope_theta"]),
        "experts": int(cfg["moe_num_primary_experts"]),
        "top_k": int(cfg["moe_num_active_primary_experts"]),
        "expert_width": int(cfg["moe_ffn_hidden_size"]),
    }


def layer_kinds(d: Dict[str, Any]) -> List[str]:
    """The attention kind a layer; every layer has the same leaves of
    the same shapes, and the two kinds differ in rotation and window."""
    return list(d["attn"])


def _shapes(d: Dict[str, Any], kind: str) -> W.Shapes:
    """(shape, fan_in) of every leaf of a layer."""
    h, dh, hkv, nh = d["hidden"], d["head_dim"], d["kv_heads"], d["heads"]
    e, f = d["experts"], d["expert_width"]
    return {
        "attn_norm": ((h,), None),
        "wq": ((h, nh, dh), h),
        "wk": ((h, hkv, dh), h),
        "wv": ((h, hkv, dh), h),
        "wo": ((nh, dh, h), nh * dh),
        "mlp_norm": ((h,), None),
        "router": ((h, e), h),
        "experts_gate": ((e, h, f), h),
        "experts_up": ((e, h, f), h),
        "experts_down": ((e, f, h), f),
    }


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
    return {
        "attn": {
            "RMSNorm_0": {"scale": w["attn_norm"]},
            "q": {"kernel": w["wq"]},
            "k": {"kernel": w["wk"]},
            "v": {"kernel": w["wv"]},
            "out": {"kernel": w["wo"]},
        },
        "RMSNorm_0": {"scale": w["mlp_norm"]},
        "moe": {
            "router": {"kernel": w["router"]},
            "experts_gate": w["experts_gate"],
            "experts_up": w["experts_up"],
            "experts_down": w["experts_down"],
        },
    }


def program_top(top: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "emb": {"embedding": top["emb"]},
        "RMSNorm_0": {"scale": top["final_norm"]},
        "lm_head": {"kernel": top["head"]},
    }


def rows_per_block(d: Dict[str, Any], pad_len: int) -> int:
    """Rows the serve comparison puts through a layer together: what a
    query block's float32 scores (heads x Q_BLOCK x pad_len x 4 B a
    row) leave of half a gigabyte, beside a layer's float32 weights
    (1.6 GB, and as much again for each control's)."""
    per_row = d["heads"] * min(Q_BLOCK, pad_len) * pad_len * 4
    return max(1, min(2, int(0.5e9 // per_row)))


def _id(x):
    return x


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, positions, theta: float):
    """x (B, S, H, D), positions (B, S): the whole head rotates,
    dimension j paired with j + D / 2."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window=None):
    """Causal grouped-query attention; q (B,S,H,D), k/v (B,S,Hkv,D);
    with ``window`` query t sees keys t - window + 1 .. t.  Computed a
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
        seen = t_q >= t_k
        if window is not None:
            seen = seen & (t_k > t_q - window)
        scores = jnp.where(seen[None, None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhgqt,bthd->bqhgd", probs, v, precision=HI)

    out = jax.lax.map(one, (qp, jnp.arange(n_blk) * blk))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, n_blk * blk, h, dh)
    return out[:, :s]


def relu(y):
    return jnp.maximum(y, 0.0)


def reglu(u, w_gate, w_up, w_down):
    gate = jnp.einsum("...d,df->...f", u, w_gate, precision=HI)
    up = jnp.einsum("...d,df->...f", u, w_up, precision=HI)
    return jnp.einsum("...f,fd->...d", relu(gate) * up, w_down, precision=HI)


def route(r_in, router, d):
    """(B, S, experts) weight of every expert for each token: the top
    ``top_k`` router LOGITS, then the softmax over those alone; 0
    elsewhere."""
    logit = jnp.einsum("bsd,de->bse", r_in, router, precision=HI)
    top, idx = jax.lax.top_k(logit, d["top_k"])
    gates = jax.nn.softmax(top, axis=-1)
    return jnp.sum(
        jax.nn.one_hot(idx, d["experts"], dtype=jnp.float32)
        * gates[..., None], axis=-2)


def router_input(h, u):
    """What the router reads, of ``h`` (the attention's normed input)
    and ``u`` (the post-attention normed state): the first."""
    return h


def routed(u, weight, w, d):
    """The routed sum: a plain loop over the experts, every token
    through each, weighted by ``weight`` (B, S, experts)."""

    def one(e, acc):
        out = reglu(u, w["experts_gate"][e], w["experts_up"][e],
                    w["experts_down"][e])
        m = jax.lax.dynamic_index_in_dim(weight, e, 2, keepdims=True)
        return acc + m * out

    return jax.lax.fori_loop(0, d["experts"], one, jnp.zeros_like(u))


def layer(x, w, positions, d, kind, round_fn=_id, kv_fn=_id):
    """One layer of ``kind``.  ``round_fn`` (training's control) is not
    applied: this architecture is only served."""
    eps = d["norm_eps"]
    h = rms_norm(x, w["attn_norm"], eps)
    q = jnp.einsum("bsd,dhk->bshk", h, w["wq"], precision=HI)
    k = jnp.einsum("bsd,dhk->bshk", h, w["wk"], precision=HI)
    v = jnp.einsum("bsd,dhk->bshk", h, w["wv"], precision=HI)
    if d["rotates"][kind]:
        q = rope(q, positions, d["rope_theta"])
        k = rope(k, positions, d["rope_theta"])
    a = attention(q, kv_fn(k), kv_fn(v), window=d["window"][kind])
    x = x + jnp.einsum("bshk,hkd->bsd", a, w["wo"], precision=HI)
    u = rms_norm(x, w["mlp_norm"], eps)
    weight = route(router_input(h, u), w["router"], d)
    return x + routed(u, weight, w, d)


def embed(ids, emb):
    return jnp.take(emb, ids, axis=0)


def logits(x, top, d, round_fn=_id):
    h = rms_norm(x, top["final_norm"], d["norm_eps"])
    return jnp.einsum("...d,dv->...v", h, top["head"], precision=HI)
