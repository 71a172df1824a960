"""LongCat-Flash-Chat's decoder (meituan-longcat; config.json as
published, the layer from the family's public description:
shortcut-connected mixture of experts over latent attention, with
zero-computation experts) in plain ``jax.numpy``: float32, every
product at ``highest`` precision, no kernel, no cache, no chunked form,
no absorbed projection, no sort.  Written from the equations, not from
the program; it imports nothing of ``mlcomp_tpu``.

Every layer is alike and holds TWO latent-attention blocks ``A0``,
``A1``, TWO dense SwiGLU MLPs ``D0``, ``D1`` and ONE routed block ``E``,
whose output joins the residual stream a whole attention + MLP later
(the shortcut).  ``N*`` are RMSNorms with learned scales:

    x1 = x  + A0(N1(x))
    h  = N2(x1)
    m  = E(h)                      # kept aside
    x2 = x1 + D0(h)
    x3 = x2 + A1(N3(x2))
    x4 = x3 + D1(N4(x3))
    out = x4 + m

**Latent attention** ``A(u)``, 64 heads: ``q = W_qb norm_q(W_qa u)``
(6144 -> 1536 -> 64 x 192), a head's ``[q_nope ; q_pe]`` (128 + 64),
both times ``s_q = (6144 / 1536)^1/2`` (``mla_scale_q_lora``); ``[c ;
k_pe] = W_kva u`` (512 + 64); ``c' = norm_kv(c) s_kv``, ``s_kv = (6144 /
512)^1/2`` (``mla_scale_kv_lora``; ``k_pe`` is not scaled); a head's
``[k_nope ; v] = W_kvb c'`` (128 + 128); ``q_pe`` and ``k_pe`` rotated
by position (theta 1e7, no scaling of positions, the pairs (2i, 2i+1)
as the family's code has them); a head's key ``[k_nope ; k_pe]``,
``k_pe`` shared by the heads; causal softmax of ``q . k / sqrt 192``;
the heads' values through ``W_o``.  No bias.  Keys and values are
expanded for every token and head, which is what the program never
does.

**Routed block** ``E(h)``: ``s = softmax(h W_r)`` in float32 over all
768 outputs, 512 real experts then 256 zero-computation ones; the top 12
of ``s + b`` (``b``: selection only); weights ``g_i = 6 s_i`` at the
chosen, NOT renormalised; a real expert is a SwiGLU of 2,048, a zero
expert the identity: ``E(h) = sum_{i real} g_i expert_i(h) + (sum_{i
zero} g_i) h``.  No shared expert.

This chip's share (``dims_of``): the real experts ``held = (first,
count)`` and a slice of the vocabulary.  A token's assignments to real
experts not held add nothing, here as in the program; the zero experts'
part, which every chip of the layer computes alike, is whole.

``assumed`` (the configuration file gives the reasoning of each): the
un-renormalised gates, both scales' formulas, theta and no scaling of
positions, the untied head, the router's bias drawn small.

The names below are the ones ``benchmark/README.md`` asks of an
architecture.  The attention runs in blocks of queries: at 8,448
positions one row's float32 scores are 64 x 8,448^2 x 4 B = 18 GB whole.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from benchmark import weights as W

HI = jax.lax.Precision.HIGHEST
# queries a block: 64 heads x 128 x 8,448 keys x 4 B = 277 MB of scores
Q_BLOCK = 128
# the router's selection bias is drawn N(0, 1 / this): 0.0005, against
# softmax scores over 768 outputs whose 12th and 13th largest lie
# ~0.0002 apart (0.02, Kimi-Linear's, would decide 11.5 of a token's 12
# choices alone, the same outputs for every token)
BIAS_FAN_IN = 4_000_000
BLOCKS = ("0", "1")          # a layer's two attention + MLP halves

TOP_LEAVES = ("emb", "final_norm", "head")

# contraction axes (a weight's quantization scale is constant along
# them).  Stacked experts are (expert, in, out).  The router, its bias
# and the norms are float32 in the program whatever the weights are: no
# entry, never rounded.
CONTRACT_AXES = {
    **{f"{name}_{b}": axes for b in BLOCKS for name, axes in (
        ("wq_a", (0,)), ("wq_b", (0,)), ("w_kva", (0,)), ("w_kvb", (0,)),
        ("wo", (0, 1)), ("w_gate", (0,)), ("w_up", (0,)), ("w_down", (0,)),
    )},
    "experts_gate": (1,), "experts_up": (1,), "experts_down": (1,),
    "emb": (0,), "head": (0,),
}


def dims_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Everything the functions below need, from a configuration file:
    the published sizes, the depth held, and this chip's share.  No
    function here reads the configuration again."""
    if cfg.get("rope_scaling") or cfg.get("attention_bias") \
            or cfg["attention_method"] != "MLA" \
            or cfg["zero_expert_type"] != "identity":
        raise NotImplementedError(
            "a RoPE scaling, attention biases, another attention method "
            "or another zero expert than the identity: none is written "
            "down"
        )
    share = cfg["share"]
    h = int(cfg["hidden_size"])
    q_rank, latent = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    return {
        "vocab": int(cfg["vocab_size"]),
        "hidden": h,
        "layers": int(cfg["num_layers"]),
        # attention blocks: two a layer
        "mixers": 2 * int(cfg["num_layers"]),
        "heads": int(cfg["num_attention_heads"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]),
        "v_dim": int(cfg["v_head_dim"]),
        "latent": latent,
        "q_rank": q_rank,
        "q_scale": (h / q_rank) ** 0.5 if cfg["mla_scale_q_lora"] else 1.0,
        "kv_scale": (h / latent) ** 0.5 if cfg["mla_scale_kv_lora"] else 1.0,
        "theta": float(cfg["rope_theta"]),
        "mlp": int(cfg["ffn_hidden_size"]),
        "norm_eps": float(cfg["as_run"]["norm_eps"]),
        "experts": int(share["experts_published"]),
        "zero_experts": int(cfg["zero_expert_num"]),
        "held": (int(share["experts_first"]), int(cfg["n_routed_experts"])),
        "top_k": int(cfg["moe_topk"]),
        "routed_scale": float(cfg["routed_scaling_factor"]),
        "expert_width": int(cfg["expert_ffn_hidden_size"]),
    }


def layer_kinds(d: Dict[str, Any]) -> List[str]:
    """Every layer is a shortcut-connected expert layer."""
    return ["shortcut"] * d["layers"]


def _shapes(d: Dict[str, Any], kind: str) -> W.Shapes:
    """(shape, fan_in) of every drawn leaf of a layer.  The two
    up-projections behind the rank scales (``wq_b``, ``w_kvb``) are
    drawn N(0, 1 / hidden), not 1 / their own rank: the variance the
    scales were designed to align, so that q, k_nope and v come out at
    unit variance and the scores at ~1 (drawn by their own ranks the
    scores' deviation is 5.8: a softmax that picks one key, which no
    precision can be held to; configuration, ``assumed.weights``)."""
    h, n, dc, rq = d["hidden"], d["heads"], d["latent"], d["q_rank"]
    f, fe, e = d["mlp"], d["expert_width"], d["held"][1]
    routed = d["experts"] + d["zero_experts"]
    shapes: W.Shapes = {}
    for b in BLOCKS:
        shapes.update({
            f"attn_norm_{b}": ((h,), None),
            f"wq_a_{b}": ((h, rq), h),
            f"q_norm_{b}": ((rq,), None),
            f"wq_b_{b}": ((rq, n, d["nope"] + d["rope"]), h),
            f"w_kva_{b}": ((h, dc + d["rope"]), h),
            f"kv_norm_{b}": ((dc,), None),
            f"w_kvb_{b}": ((dc, n, d["nope"] + d["v_dim"]), h),
            f"wo_{b}": ((n, d["v_dim"], h), n * d["v_dim"]),
            f"mlp_norm_{b}": ((h,), None),
            f"w_gate_{b}": ((h, f), h),
            f"w_up_{b}": ((h, f), h),
            f"w_down_{b}": ((f, h), f),
        })
    shapes.update({
        "router": ((h, routed), h),
        "router_bias": ((routed,), BIAS_FAN_IN),
        "experts_gate": ((e, h, fe), h),
        "experts_up": ((e, h, fe), h),
        "experts_down": ((e, fe, h), fe),
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


def halves_first(rope: int):
    """Where the program keeps the rotating columns: it pairs column j
    with j + rope / 2, so the published pairs' first members (0, 2, ...)
    come first and their second members (1, 3, ...) after them.  The
    same permutation of ``q_pe``'s and ``k_pe``'s columns leaves every
    score what it was."""
    return jnp.concatenate([jnp.arange(0, rope, 2), jnp.arange(1, rope, 2)])


def program_layer(w: Dict[str, Any], kind: str) -> Dict[str, Any]:
    """One layer in the parameter layout of ``mixed_layer_lm``: the two
    halves' leaves under ``attn`` / ``attn_1``, ``RMSNorm_0`` / ``_1``,
    ``gate`` / ``gate_1`` ..., the rotating columns of ``q_b`` and
    ``kv_a`` in the program's order (``halves_first``)."""
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    out: Dict[str, Any] = {}
    for b, tail in zip(BLOCKS, ("", "_1")):
        wq_b, w_kva = w[f"wq_b_{b}"], w[f"w_kva_{b}"]
        # kv_a's columns past the latent's rank are k_pe
        rope = w_kva.shape[-1] - w[f"w_kvb_{b}"].shape[0]
        order = halves_first(rope)
        out["attn" + tail] = {
            "RMSNorm_0": {"scale": w[f"attn_norm_{b}"]},
            "q_a": {"kernel": w[f"wq_a_{b}"]},
            "q_norm": w[f"q_norm_{b}"],
            "q_b": {"kernel": jnp.concatenate([
                wq_b[..., :-rope], wq_b[..., -rope:][..., order]], -1)},
            "kv_a": {"kernel": jnp.concatenate([
                w_kva[:, :-rope], w_kva[:, -rope:][:, order]], -1)},
            "kv_norm": w[f"kv_norm_{b}"],
            "kv_b": w[f"w_kvb_{b}"],
            "out": {"kernel": w[f"wo_{b}"]},
        }
        out["RMSNorm" + (tail or "_0")] = {"scale": w[f"mlp_norm_{b}"]}
        for name in ("gate", "up", "down"):
            out[name + tail] = {"kernel": w[f"w_{name}_{b}"]}
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
    """Rows the serve comparison puts through a layer together.  A
    row's expanded queries, keys and values are heads x pad_len x (192 +
    192 + 256 + 128) x 4 B (1.66 GB at 8,448) and a query block's scores
    and weights heads x 2 Q_BLOCK x pad_len x 4 B (0.55 GB), beside a
    layer's float32 weights (4.97 GB, and as much again for the
    control's): one row at the cell's length, more of shorter ones."""
    per_row = d["heads"] * pad_len * 4 * (
        2 * (d["nope"] + d["rope"]) + d["nope"] + 2 * d["v_dim"]
        + 2 * min(Q_BLOCK, pad_len)
    )
    return max(1, min(8, int(2.5e9 // per_row)))


def _id(x):
    return x


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rotate(x, positions, d):
    """``x`` (B, S, ..., rope): columns (2i, 2i + 1) turned by the angle
    ``position x theta^(-2i / rope)``."""
    rope = x.shape[-1]
    inv = d["theta"] ** (-jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    ang = positions.astype(jnp.float32)[..., None] * inv      # (B, S, rope/2)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[2:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [even * cos - odd * sin, odd * cos + even * sin], axis=-1
    ).reshape(x.shape)


def kv_scale(d):
    """What the normed latent is multiplied by."""
    return d["kv_scale"]


def attention(q, k, v):
    """Causal attention; q, k (B, S, H, D), v (B, S, H, Dv): every query
    against every earlier token, a block of Q_BLOCK queries at a time."""
    b, s, h, dq = q.shape
    blk = min(Q_BLOCK, s)
    n_blk = -(-s // blk)
    qp = jnp.pad(q, ((0, 0), (0, n_blk * blk - s), (0, 0), (0, 0)))
    qp = jnp.moveaxis(qp.reshape(b, n_blk, blk, h, dq), 1, 0)
    t_k = jnp.arange(s)[None, :]

    def one(args):
        qb, first = args
        t_q = first + jnp.arange(blk)
        scores = jnp.einsum("bqhd,bthd->bhqt", qb, k, precision=HI)
        scores = scores / jnp.sqrt(jnp.float32(dq))
        scores = jnp.where((t_q[:, None] >= t_k)[None, None], scores,
                           -jnp.inf)
        return jnp.einsum("bhqt,bthd->bqhd", jax.nn.softmax(scores, axis=-1),
                          v, precision=HI)

    out = jax.lax.map(one, (qp, jnp.arange(n_blk) * blk))
    return jnp.moveaxis(out, 0, 1).reshape(b, n_blk * blk, h, -1)[:, :s]


def mla(u, w, blk, positions, d):
    """Latent attention block ``blk`` (``"0"`` or ``"1"``) of the normed
    input ``u``: what it adds to the residual stream."""
    b, s, _ = u.shape
    n, dc, nope = d["heads"], d["latent"], d["nope"]
    low = rms_norm(jnp.einsum("bsd,dr->bsr", u, w[f"wq_a_{blk}"],
                              precision=HI),
                   w[f"q_norm_{blk}"], d["norm_eps"])
    q = jnp.einsum("bsr,rhk->bshk", low, w[f"wq_b_{blk}"], precision=HI)
    q = q * d["q_scale"]
    kv = jnp.einsum("bsd,dc->bsc", u, w[f"w_kva_{blk}"], precision=HI)
    c = rms_norm(kv[..., :dc], w[f"kv_norm_{blk}"], d["norm_eps"]) \
        * kv_scale(d)
    k_pe = rotate(kv[..., dc:], positions, d)
    q = jnp.concatenate(
        [q[..., :nope], rotate(q[..., nope:], positions, d)], axis=-1)
    up = jnp.einsum("bsc,chk->bshk", c, w[f"w_kvb_{blk}"], precision=HI)
    k = jnp.concatenate([
        up[..., :nope],
        jnp.broadcast_to(k_pe[:, :, None, :], (b, s, n, d["rope"])),
    ], axis=-1)
    a = attention(q, k, up[..., nope:])
    return jnp.einsum("bshk,hkd->bsd", a, w[f"wo_{blk}"], precision=HI)


def swiglu(h, w_gate, w_up, w_down):
    gate = jnp.einsum("...d,df->...f", h, w_gate, precision=HI)
    up = jnp.einsum("...d,df->...f", h, w_up, precision=HI)
    return jnp.einsum("...f,fd->...d", jax.nn.silu(gate) * up, w_down,
                      precision=HI)


def gates(chosen_scores, d):
    """The weights of a token's chosen experts from their scores (zeros
    elsewhere): as they are, times the scale; not renormalised."""
    return chosen_scores * d["routed_scale"]


def route(u, w, d):
    """(B, S, experts + zero experts) weight of every output of the
    router for each token: softmax over all of them, the ``top_k`` of
    score + bias, ``gates`` of the scores at the chosen, 0 elsewhere."""
    s = jax.nn.softmax(
        jnp.einsum("bsd,de->bse", u, w["router"], precision=HI), axis=-1)
    _, idx = jax.lax.top_k(s + w["router_bias"], d["top_k"])
    chosen = jax.nn.one_hot(idx, s.shape[-1], dtype=jnp.float32).sum(-2)
    return gates(s * chosen, d)


def zero_part(u, weight, d):
    """What the zero-computation experts add: each the identity, so the
    sum of their weights times the input."""
    return jnp.sum(weight[..., d["experts"]:], axis=-1, keepdims=True) * u


def routed(u, w, d, held=None):
    """The held real experts' part of the routed sum, by a plain loop
    over them (every token through each, weighted by ``route``), plus
    the zero experts' part whole."""
    first, count = d["held"] if held is None else held
    weight = route(u, w, d)

    def one(e, acc):
        out = swiglu(u, w["experts_gate"][e], w["experts_up"][e],
                     w["experts_down"][e])
        m = jax.lax.dynamic_index_in_dim(weight, first + e, 2, keepdims=True)
        return acc + m * out

    real = jax.lax.fori_loop(0, count, one, jnp.zeros_like(u))
    return real + zero_part(u, weight, d)


def layer(x, w, positions, d, kind, round_fn=_id, kv_fn=_id):
    """One shortcut-connected layer.  ``round_fn`` (training's control)
    is not applied: this architecture is only served; ``kv_fn`` neither:
    the configuration states no rounding of what the cache keeps."""
    norm = lambda x, name: rms_norm(x, w[name], d["norm_eps"])  # noqa: E731
    mlp = lambda h, b: swiglu(  # noqa: E731
        h, w[f"w_gate_{b}"], w[f"w_up_{b}"], w[f"w_down_{b}"])
    x1 = x + mla(norm(x, "attn_norm_0"), w, "0", positions, d)
    h = norm(x1, "mlp_norm_0")
    aside = routed(h, w, d)
    x2 = x1 + mlp(h, "0")
    x3 = x2 + mla(norm(x2, "attn_norm_1"), w, "1", positions, d)
    x4 = x3 + mlp(norm(x3, "mlp_norm_1"), "1")
    return x4 + aside


def embed(ids, emb):
    return jnp.take(emb, ids, axis=0)


def logits(x, top, d, round_fn=_id):
    h = rms_norm(x, top["final_norm"], d["norm_eps"])
    return jnp.einsum("...d,dv->...v", h, top["head"], precision=HI)
