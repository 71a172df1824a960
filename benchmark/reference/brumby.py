"""Brumby-14B-Base's decoder (manifestai; config.json as published, the
layer from the public description of power retention, arXiv:2507.04239,
and the model's release notes) in plain ``jax.numpy``: float32, every
product at ``highest`` precision, no kernel, no cache, no recurrent
state.  Written from the equations, not from the program; it imports
nothing of ``mlcomp_tpu``.

All 40 layers are alike: 40 query heads over 8 KV heads of 128, no
biases but the gate's, a SwiGLU MLP of 17,408.  No layer has softmax
attention.  A layer is the pre-norm residual block
``x + Out(retention(RMSNorm(x)))``, then ``x + MLP(RMSNorm(x))`` with
``MLP = down(silu(gate u) * up u)``.  With ``h_t`` the normed input of
token ``t``:

- ``q_t = W_q h_t`` (40 heads x 128), ``k_t = W_k h_t``,
  ``v_t = W_v h_t`` (8 heads x 128), ``gamma_t = W_g h_t + b_g`` (8: one
  gate a KV head);
- ``q`` and ``k``: RMSNorm over each head's 128 (a learned 128-vector
  each, eps 1e-6), then RoPE at ``rope_theta`` 1e6 over the whole head,
  dimension ``j`` paired with ``j + 64``;
- ``log g_t = log sigmoid(gamma_t)``, float32; query head ``h`` reads KV
  head ``n = h // 5``;
- ``a_{t,s} = exp(sum_{j=s+1..t} log g_j^(n)) * (q_t^(h) . k_s^(n))^2``
  for ``s <= t`` (degree 2, even, so ``a >= 0``; a constant scale
  inside the square would cancel below);
- ``o_t^(h) = sum_s a_{t,s} v_s^(n) / (sum_s a_{t,s} + 1e-6)``; the
  output is ``W_o . concat_h o_t^(h)``.  No output gate, no output norm.

The same function is a recurrence over a state of fixed size (with
``phi`` the symmetric second power, ``<phi(q), phi(k)> = (q . k)^2``:
``S_t = g_t S_{t-1} + phi(k_t) v_t^T``, ``z_t = g_t z_{t-1} + phi(k_t)``,
``o_t = S_t^T phi(q_t) / (z_t . phi(q_t) + eps)``), which is how the
program serves it.  This file never forms ``phi``: it squares ``q . k``,
token against token, so it shares nothing with the program's state.

``assumed`` (the configuration file gives the reasoning of each): the
degree, one gate a KV head and its bias, the q/k norm, RoPE kept, the
normaliser and its eps, no output gate.  The gate's bias is not drawn:
one constant a KV head, evenly from 3 to 8.

The names below are the ones ``benchmark/README.md`` asks of an
architecture.  The retention runs in blocks of queries: at 4,352
positions one row's float32 weights are 40 x 4,352^2 x 4 B = 3 GB whole.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from benchmark import weights as W

HI = jax.lax.Precision.HIGHEST
# queries a block: 40 heads x 256 x 4,352 keys x 4 B = 178 MB of weights
Q_BLOCK = 256
# the normaliser's epsilon
EPS = 1e-6
# the gate's bias, first KV head to last: memories of ~20 to ~3,000 tokens
GATE_BIAS = (3.0, 8.0)

TOP_LEAVES = ("emb", "final_norm", "head")

# contraction axes (a weight's quantization scale is constant along
# them).  The gate's projection and bias and the norms are float32 in
# the program whatever the weights are: no entry, never rounded.
CONTRACT_AXES = {
    "wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
    "w_gate": (0,), "w_up": (0,), "w_down": (0,),
    "emb": (0,), "head": (0,),
}


def dims_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Everything the functions below need, from a configuration file.
    No function here reads the configuration again."""
    if cfg.get("rope_scaling") is not None:
        raise NotImplementedError("rope_scaling: only null is written down")
    if cfg.get("use_sliding_window") or cfg.get("attention_bias"):
        raise NotImplementedError(
            "a window or a projection bias: neither is written down"
        )
    return {
        "vocab": int(cfg["vocab_size"]),
        "hidden": int(cfg["hidden_size"]),
        "layers": int(cfg["num_hidden_layers"]),
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["head_dim"]),
        "mlp": int(cfg["intermediate_size"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "rope_theta": float(cfg["rope_theta"]),
        # the least a symmetric second power of a head needs
        "expanded": int(cfg["head_dim"]) * (int(cfg["head_dim"]) + 1) // 2,
    }


def layer_kinds(d: Dict[str, Any]) -> List[str]:
    return ["retention"] * d["layers"]


def _shapes(d: Dict[str, Any], kind: str) -> W.Shapes:
    """(shape, fan_in) of every drawn leaf of a layer."""
    h, dh, hkv, nh, f = (d["hidden"], d["head_dim"], d["kv_heads"],
                         d["heads"], d["mlp"])
    return {
        "attn_norm": ((h,), None),
        "wq": ((h, nh, dh), h),
        "wk": ((h, hkv, dh), h),
        "wv": ((h, hkv, dh), h),
        "wo": ((nh, dh, h), nh * dh),
        "wg": ((h, hkv), h),
        "q_norm": ((dh,), None),
        "k_norm": ((dh,), None),
        "mlp_norm": ((h,), None),
        "w_gate": ((h, f), h),
        "w_up": ((h, f), h),
        "w_down": ((f, h), f),
    }


def layer_weights(key, i, d, dtype, kind) -> Dict[str, Any]:
    w = W.layer_leaves(key, i, _shapes(d, kind), dtype)
    # set, not drawn: gamma ~ N(0, 1) would forget in two tokens, and no
    # comparison would see a state carried wrongly
    w["bg"] = jnp.linspace(*GATE_BIAS, d["kv_heads"], dtype=jnp.float32)
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
    return {
        "attn": {
            "RMSNorm_0": {"scale": w["attn_norm"]},
            "q": {"kernel": w["wq"]},
            "k": {"kernel": w["wk"]},
            "v": {"kernel": w["wv"]},
            "out": {"kernel": w["wo"]},
            "gate": {"kernel": w["wg"].astype(jnp.float32), "bias": w["bg"]},
            "q_norm": w["q_norm"],
            "k_norm": w["k_norm"],
        },
        "RMSNorm_0": {"scale": w["mlp_norm"]},
        "gate": {"kernel": w["w_gate"]},
        "up": {"kernel": w["w_up"]},
        "down": {"kernel": w["w_down"]},
    }


def program_top(top: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "emb": {"embedding": top["emb"]},
        "RMSNorm_0": {"scale": top["final_norm"]},
        "lm_head": {"kernel": top["head"]},
    }


def rows_per_block(d: Dict[str, Any], pad_len: int) -> int:
    """Rows the serve comparison puts through a layer together: what a
    query block's float32 weights (heads x Q_BLOCK x pad_len x 4 B a
    row) leave of half a gigabyte, beside a layer's float32 weights
    (1.3 GB, and as much again for the control's)."""
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


def log_gate(gamma):
    """``log g`` of the gate's pre-activation."""
    return jax.nn.log_sigmoid(gamma)


def power(dots):
    """The kernel of a query-key product: degree 2."""
    return dots * dots


def normalised(num, den):
    """The weighted sum over the sum of the weights."""
    return num / (den + EPS)


def retention(q, k, v, log_g):
    """``q`` (B, S, H, D), ``k``/``v`` (B, S, Hkv, D), ``log_g``
    (B, S, Hkv): every query against every earlier token, a block of
    Q_BLOCK queries at a time."""
    b, s, h, dh = q.shape
    hkv = k.shape[2]
    blk = min(Q_BLOCK, s)
    n_blk = -(-s // blk)
    qp = jnp.pad(q, ((0, 0), (0, n_blk * blk - s), (0, 0), (0, 0)))
    qp = qp.reshape(b, n_blk, blk, hkv, h // hkv, dh).transpose(1, 0, 2, 3, 4, 5)
    # the log gates summed up to and with each token: (B, Hkv, S)
    cum = jnp.cumsum(log_g, axis=1).transpose(0, 2, 1)
    t_k = jnp.arange(s)[None, :]

    def one(args):
        qb, first = args
        # a pad query past the end stands where the last real one does
        t_q = jnp.minimum(first + jnp.arange(blk), s - 1)
        dots = jnp.einsum("bqhgd,bthd->bhgqt", qb, k, precision=HI)
        seen = t_q[:, None] >= t_k
        between = jnp.take(cum, t_q, axis=2)[..., None] - cum[:, :, None, :]
        decay = jnp.exp(jnp.where(seen[None, None], between, -jnp.inf))
        a = power(dots) * decay[:, :, None]
        num = jnp.einsum("bhgqt,bthd->bqhgd", a, v, precision=HI)
        den = a.sum(-1).transpose(0, 3, 1, 2)[..., None]
        return normalised(num, den)

    out = jax.lax.map(one, (qp, jnp.arange(n_blk) * blk))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, n_blk * blk, h, dh)
    return out[:, :s]


def layer(x, w, positions, d, kind, round_fn=_id, kv_fn=_id):
    """One layer.  ``round_fn`` (training's control) is not applied:
    this architecture is only served; there are no keys and values for
    ``kv_fn`` to round."""
    eps = d["norm_eps"]
    h = rms_norm(x, w["attn_norm"], eps)
    q = jnp.einsum("bsd,dhk->bshk", h, w["wq"], precision=HI)
    k = jnp.einsum("bsd,dhk->bshk", h, w["wk"], precision=HI)
    v = jnp.einsum("bsd,dhk->bshk", h, w["wv"], precision=HI)
    gamma = jnp.einsum("bsd,dn->bsn", h, w["wg"], precision=HI) + w["bg"]
    q = rope(rms_norm(q, w["q_norm"], eps), positions, d["rope_theta"])
    k = rope(rms_norm(k, w["k_norm"], eps), positions, d["rope_theta"])
    a = retention(q, k, v, log_gate(gamma))
    x = x + jnp.einsum("bshk,hkd->bsd", a, w["wo"], precision=HI)
    u = rms_norm(x, w["mlp_norm"], eps)
    gate = jnp.einsum("bsd,df->bsf", u, w["w_gate"], precision=HI)
    up = jnp.einsum("bsd,df->bsf", u, w["w_up"], precision=HI)
    return x + jnp.einsum(
        "bsf,fd->bsd", jax.nn.silu(gate) * up, w["w_down"], precision=HI
    )


def embed(ids, emb):
    return jnp.take(emb, ids, axis=0)


def logits(x, top, d, round_fn=_id):
    h = rms_norm(x, top["final_norm"], d["norm_eps"])
    return jnp.einsum("...d,dv->...v", h, top["head"], precision=HI)
