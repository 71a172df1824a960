"""Kimi-Linear-48B-A3B-Instruct's decoder (moonshotai; config.json as
published, the layers from the public description of Kimi Delta
Attention, arXiv:2510.26692) in plain ``jax.numpy``: float32, every
product at ``highest`` precision, no kernel, no cache, no chunked form,
no absorbed projection, no sort.  Written from the equations, not from
the program; it imports nothing of ``mlcomp_tpu``.

Layer ``l`` (1-indexed, as ``linear_attn_config`` counts) is a KDA
layer or a latent-attention (MLA) layer, by the config's two lists; the
first ``first_k_dense_replace`` layers' MLP is a dense SwiGLU, the
others' routed experts.  With ``h = RMSNorm(x)`` and pre-norm residuals:

**KDA** (32 heads of 128, convolution of 4 taps).  For token ``t``,
head ``n``:

- ``q~, k~, v~ = h W_q, h W_k, h W_v``; along the sequence, each channel
  of each stream through its own causal convolution (``y_t = sum_i
  w_i x_{t-3+i}``, zeros before the first token), then SiLU;
- ``q = 128^-1/2 q' / |q'|_2`` and ``k = k' / |k'|_2`` a head (inside
  the root ``+ 1e-6``);
- the log-decay a key CHANNEL ``g_t = -exp(A_log[n]) softplus(W_fb (W_fa
  h) + dt_bias)`` (2,304 -> 128 -> 32 x 128), ``alpha_t = exp g_t``;
  ``beta_t = sigmoid(h w_beta[n])``;
- the state ``S`` (key x value, 128 x 128, zero before the first
  token): ``S' = Diag(alpha_t) S_{t-1}``; ``S_t = S' + beta_t k_t (v_t -
  S'^T k_t)^T``; ``o_t = S_t^T q_t``;
- ``x += W_o [RMSNorm_head(o_t) * sigmoid(W_gb (W_ga h))]`` (a learned
  128-vector a head width; the gate 2,304 -> 128 -> 32 x 128).

This file walks the recurrence a token at a time (``lax.scan``), which
is how the layer is defined; the program's chunked form and its kernel
share nothing with it.

**MLA** (32 heads; ``mla_use_nope``: nothing is rotated).  ``q = h
W_q`` (a head: 128 + 64); ``[c ; k_pe] = h W_kva`` (512 + 64); ``c <-
RMSNorm(c)``; a head's ``[k_nope ; v] = c W_kvb`` (128 + 128); its key
``[k_nope ; k_pe]``, ``k_pe`` shared by the heads; causal softmax of
``q . k / sqrt 192``; ``x += W_o [sum p v]``.  Keys and values are
expanded for every token and head, which is what the program never
does.

**Experts**: ``s = sigmoid(W_r u)`` (float32, all 256 published
experts); the top 8 of ``s + b`` (``b`` a number an expert: selection
only; ``num_expert_group`` 1 is no group limit); weights ``s`` at the
chosen, renormalised to sum 1, times 2.446; SwiGLU experts of 1,024;
plus one shared SwiGLU expert on every token, unscaled.

This chip's share (``dims_of``): the experts ``held = (first, count)``
and a slice of the vocabulary.  A token's assignments to experts not
held add nothing, here as in the program; the partial result goes on.

``assumed`` (the configuration file gives the reasoning of each): the
two gates' low-rank forms, no biases but ``dt_bias``, the convolution
without bias, ``A_log`` and ``dt_bias`` SET (not drawn), the L2 norm's
epsilon, the unrotated ``k_pe``, the router's bias drawn small.

The names below are the ones ``benchmark/README.md`` asks of an
architecture.  The attention runs in blocks of queries: at 9,728
positions one row's float32 scores are 32 x 9,728^2 x 4 B = 12 GB whole.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from benchmark import weights as W

HI = jax.lax.Precision.HIGHEST
# queries a block: 32 heads x 128 x 9,728 keys x 4 B = 159 MB of scores
Q_BLOCK = 128
L2_EPS = 1e-6
# exp(A_log), first head to last: with softplus(0) = 0.69 a token's
# decay runs from ~0.95 to ~0.9997 (memories of ~20 to ~3,000 tokens)
DECAY_RATES = (0.074, 0.00043)
# the router's selection bias is drawn N(0, 1 / this): ~0.02, against
# scores that differ by ~0.2 from one expert to the next
BIAS_FAN_IN = 2500

TOP_LEAVES = ("emb", "final_norm", "head")

# contraction axes (a weight's quantization scale is constant along
# them).  Stacked experts are (expert, in, out).  The router and its
# bias, the decay's and beta's projections, the convolution's taps and
# the norms are float32 in the program whatever the weights are: no
# entry, never rounded.
CONTRACT_AXES = {
    "wq": (0,), "wk": (0,), "wv": (0,), "wg_a": (0,), "wg_b": (0,),
    "w_kva": (0,), "w_kvb": (0,), "wo": (0, 1),
    "w_gate": (0,), "w_up": (0,), "w_down": (0,),
    "experts_gate": (1,), "experts_up": (1,), "experts_down": (1,),
    "shared_gate": (0,), "shared_up": (0,), "shared_down": (0,),
    "emb": (0,), "head": (0,),
}


def dims_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Everything the functions below need, from a configuration file:
    the published sizes, the layer pattern cut to the layers held, and
    this chip's share.  No function here reads the configuration again."""
    if cfg.get("q_lora_rank") is not None or cfg.get("rope_scaling"):
        raise NotImplementedError(
            "a low-rank q or a RoPE scaling: neither is written down"
        )
    if not cfg["mla_use_nope"] or cfg["num_expert_group"] != 1 \
            or cfg["moe_router_activation_func"] != "sigmoid" \
            or not cfg["moe_renormalize"] or cfg["num_shared_experts"] != 1:
        raise NotImplementedError(
            "rotated latent attention, grouped top-k, another router "
            "score, no renormalisation or another count of shared "
            "experts: none is written down"
        )
    n = int(cfg["num_hidden_layers"])
    lin = cfg["linear_attn_config"]
    share = cfg["share"]
    return {
        "vocab": int(cfg["vocab_size"]),
        "hidden": int(cfg["hidden_size"]),
        "layers": n,
        "attn": ["kda" if i + 1 in lin["kda_layers"] else "latent"
                 for i in range(n)],
        "dense_layers": int(cfg["first_k_dense_replace"]),
        "heads": int(cfg["num_attention_heads"]),
        "kda_heads": int(lin["num_heads"]),
        "kda_dim": int(lin["head_dim"]),
        "conv": int(lin["short_conv_kernel_size"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]),
        "v_dim": int(cfg["v_head_dim"]),
        "latent": int(cfg["kv_lora_rank"]),
        "mlp": int(cfg["intermediate_size"]),
        "norm_eps": float(cfg["as_run"]["norm_eps"]),
        "experts": int(share["experts_published"]),
        "held": (int(share["experts_first"]), int(cfg["num_experts"])),
        "top_k": int(cfg["num_experts_per_token"]),
        "routed_scale": float(cfg["routed_scaling_factor"]),
        "expert_width": int(cfg["moe_intermediate_size"]),
    }


def layer_kinds(d: Dict[str, Any]) -> List[str]:
    """``"kda_dense"`` (KDA, then the dense MLP), ``"kda"`` and
    ``"latent"`` (each with the experts); layers of one name have the
    same leaves of the same shapes."""
    kinds = [a + ("_dense" if i < d["dense_layers"] else "")
             for i, a in enumerate(d["attn"])]
    if "latent_dense" in kinds:
        raise NotImplementedError("a latent layer with the dense MLP")
    return kinds


def _shapes(d: Dict[str, Any], kind: str) -> W.Shapes:
    """(shape, fan_in) of every drawn leaf of a layer of ``kind``."""
    h = d["hidden"]
    shapes: W.Shapes = {"attn_norm": ((h,), None)}
    if kind.startswith("kda"):
        n, dh, taps = d["kda_heads"], d["kda_dim"], d["conv"]
        shapes.update({
            "wq": ((h, n * dh), h), "wk": ((h, n * dh), h),
            "wv": ((h, n * dh), h),
            "conv": ((taps, 3 * n * dh), taps),
            "wf_a": ((h, dh), h), "wf_b": ((dh, n * dh), dh),
            "w_beta": ((h, n), h),
            "wg_a": ((h, dh), h), "wg_b": ((dh, n * dh), dh),
            "o_norm": ((dh,), None),
            "wo": ((n, dh, h), n * dh),
        })
    else:
        n, dc = d["heads"], d["latent"]
        shapes.update({
            "wq": ((h, n, d["nope"] + d["rope"]), h),
            "w_kva": ((h, dc + d["rope"]), h),
            "kv_norm": ((dc,), None),
            "w_kvb": ((dc, n, d["nope"] + d["v_dim"]), dc),
            "wo": ((n, d["v_dim"], h), n * d["v_dim"]),
        })
    shapes["mlp_norm"] = ((h,), None)
    if kind.endswith("dense"):
        f = d["mlp"]
        shapes.update({"w_gate": ((h, f), h), "w_up": ((h, f), h),
                       "w_down": ((f, h), f)})
    else:
        e, f = d["held"][1], d["expert_width"]
        shapes.update({
            "router": ((h, d["experts"]), h),
            "router_bias": ((d["experts"],), BIAS_FAN_IN),
            "experts_gate": ((e, h, f), h),
            "experts_up": ((e, h, f), h),
            "experts_down": ((e, f, h), f),
            "shared_gate": ((h, f), h),
            "shared_up": ((h, f), h),
            "shared_down": ((f, h), f),
        })
    return shapes


def layer_weights(key, i, d, dtype, kind) -> Dict[str, Any]:
    w = W.layer_leaves(key, i, _shapes(d, kind), dtype)
    if kind.startswith("kda"):
        # set, not drawn: a rate drawn around 1 would forget in two
        # tokens, and no comparison would see a state carried wrongly
        lo, hi = (math.log(r) for r in DECAY_RATES)
        w["a_log"] = jnp.linspace(lo, hi, d["kda_heads"], dtype=jnp.float32)
        w["dt_bias"] = jnp.zeros((d["kda_heads"] * d["kda_dim"],),
                                 jnp.float32)
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
    if kind.startswith("kda"):
        attn = {
            "q": {"kernel": w["wq"]}, "k": {"kernel": w["wk"]},
            "v": {"kernel": w["wv"]},
            "conv": f32(w["conv"]),
            "decay_a": {"kernel": f32(w["wf_a"])},
            "decay_b": {"kernel": f32(w["wf_b"])},
            "A_log": w["a_log"], "dt_bias": w["dt_bias"],
            "beta": {"kernel": f32(w["w_beta"])},
            "gate_a": {"kernel": w["wg_a"]}, "gate_b": {"kernel": w["wg_b"]},
            "o_norm": w["o_norm"],
        }
    else:
        attn = {
            "q": {"kernel": w["wq"]}, "kv_a": {"kernel": w["w_kva"]},
            "kv_norm": w["kv_norm"], "kv_b": w["w_kvb"],
        }
    out = {
        "attn": {"RMSNorm_0": {"scale": w["attn_norm"]},
                 "out": {"kernel": w["wo"]}, **attn},
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
    """Rows the serve comparison puts through a layer together.  A
    row's expanded keys and values are heads x pad_len x (192 + 128) x
    4 B (398 MB at 9,728) and a query block's scores heads x Q_BLOCK x
    pad_len x 4 B (159 MB), beside a sparse layer's float32 weights
    (1.1 GB, and as much again for the control's): two rows at the
    cell's length, more of shorter ones."""
    per_row = d["heads"] * pad_len * 4 * (
        d["nope"] + d["rope"] + d["v_dim"] + 2 * min(Q_BLOCK, pad_len)
    )
    return max(1, min(8, int(1.5e9 // per_row)))


def _id(x):
    return x


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def causal_conv(x, taps):
    """``x`` (B, S, C), ``taps`` (T, C): ``y_t = sum_i taps[i] x_{t - T
    + 1 + i}``, zeros before the first token."""
    t, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (t - 1, 0), (0, 0)))
    return sum(taps[i] * padded[:, i:i + s] for i in range(t))


def log_decay(a_log, pre):
    """``g`` of the decay's pre-activation ``pre`` (B, S, N, dh)."""
    return -jnp.exp(a_log)[:, None] * jax.nn.softplus(pre)


def erased(state, k):
    """What the decayed state already says of ``k``: ``S'^T k``."""
    return jnp.einsum("bncd,bnc->bnd", state, k, precision=HI)


def delta_rule(q, k, v, g, beta):
    """The recurrence, a token at a time.  ``q``, ``k``, ``v``, ``g``
    (B, S, N, dh), ``beta`` (B, S, N); returns ``o`` (B, S, N, dh)."""
    b, _, n, dh = q.shape

    def token(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = jnp.exp(g_t)[..., None] * state
        u = beta_t[..., None] * (v_t - erased(state, k_t))
        state = state + k_t[..., None] * u[..., None, :]
        return state, jnp.einsum("bncd,bnc->bnd", state, q_t, precision=HI)

    seq = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
    _, out = jax.lax.scan(
        token, jnp.zeros((b, n, dh, dh), jnp.float32),
        (seq(q), seq(k), seq(v), seq(g), seq(beta)),
    )
    return jnp.moveaxis(out, 0, 1)


def kda(x, w, d):
    b, s, _ = x.shape
    n, dh = d["kda_heads"], d["kda_dim"]
    h = rms_norm(x, w["attn_norm"], d["norm_eps"])
    proj = lambda m: jnp.einsum("bsd,dc->bsc", h, m, precision=HI)  # noqa: E731
    streams = jnp.concatenate([proj(w["wq"]), proj(w["wk"]), proj(w["wv"])],
                              axis=-1)
    mixed = jax.nn.silu(causal_conv(streams, w["conv"]))
    q, k, v = jnp.split(mixed.reshape(b, s, 3 * n, dh), 3, axis=2)
    q, k = l2_norm(q) * dh ** -0.5, l2_norm(k)
    low = lambda a, m: jnp.einsum("bsd,dc->bsc", a, m, precision=HI)  # noqa: E731
    pre = low(low(h, w["wf_a"]), w["wf_b"]) + w["dt_bias"]
    g = log_decay(w["a_log"], pre.reshape(b, s, n, dh))
    beta = jax.nn.sigmoid(proj(w["w_beta"]))
    o = rms_norm(delta_rule(q, k, v, g, beta), w["o_norm"], d["norm_eps"])
    gate = jax.nn.sigmoid(low(low(h, w["wg_a"]), w["wg_b"]))
    return x + jnp.einsum("bshk,hkd->bsd", o * gate.reshape(b, s, n, dh),
                          w["wo"], precision=HI)


def shared_key(k_pe, positions):
    """The part of the key every head shares: as it is, not rotated."""
    del positions
    return k_pe


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


def mla(x, w, positions, d):
    b, s, _ = x.shape
    n, dc = d["heads"], d["latent"]
    h = rms_norm(x, w["attn_norm"], d["norm_eps"])
    q = jnp.einsum("bsd,dhk->bshk", h, w["wq"], precision=HI)
    kv = jnp.einsum("bsd,dc->bsc", h, w["w_kva"], precision=HI)
    c = rms_norm(kv[..., :dc], w["kv_norm"], d["norm_eps"])
    k_pe = shared_key(kv[..., dc:], positions)
    up = jnp.einsum("bsc,chk->bshk", c, w["w_kvb"], precision=HI)
    k = jnp.concatenate([
        up[..., :d["nope"]],
        jnp.broadcast_to(k_pe[:, :, None, :], (b, s, n, d["rope"])),
    ], axis=-1)
    a = attention(q, k, up[..., d["nope"]:])
    return x + jnp.einsum("bshk,hkd->bsd", a, w["wo"], precision=HI)


def swiglu(h, w_gate, w_up, w_down):
    gate = jnp.einsum("...d,df->...f", h, w_gate, precision=HI)
    up = jnp.einsum("...d,df->...f", h, w_up, precision=HI)
    return jnp.einsum("...f,fd->...d", jax.nn.silu(gate) * up, w_down,
                      precision=HI)


def router_scores(logits):
    """An expert's score: the logistic function of its own logit."""
    return jax.nn.sigmoid(logits)


def route(u, w, d):
    """(B, S, experts) weight of every published expert for each token:
    the ``top_k`` of score + bias; the scores of the chosen
    renormalised to sum 1 and scaled; 0 elsewhere."""
    s = router_scores(jnp.einsum("bsd,de->bse", u, w["router"], precision=HI))
    _, idx = jax.lax.top_k(s + w["router_bias"], d["top_k"])
    chosen = jax.nn.one_hot(idx, d["experts"], dtype=jnp.float32).sum(-2)
    picked = s * chosen
    return picked / jnp.sum(picked, axis=-1, keepdims=True) * d["routed_scale"]


def routed(u, w, d, held=None):
    """The held experts' part of the routed sum: a plain loop over the
    experts held, every token through each, weighted by ``route``."""
    first, count = d["held"] if held is None else held
    weight = route(u, w, d)

    def one(e, acc):
        out = swiglu(u, w["experts_gate"][e], w["experts_up"][e],
                     w["experts_down"][e])
        m = jax.lax.dynamic_index_in_dim(weight, first + e, 2, keepdims=True)
        return acc + m * out

    return jax.lax.fori_loop(0, count, one, jnp.zeros_like(u))


def layer(x, w, positions, d, kind, round_fn=_id, kv_fn=_id):
    """One layer of ``kind``.  ``round_fn`` (training's control) is not
    applied: this architecture is only served; ``kv_fn`` neither: the
    configuration states no rounding of what the caches keep."""
    x = kda(x, w, d) if kind.startswith("kda") else mla(x, w, positions, d)
    u = rms_norm(x, w["mlp_norm"], d["norm_eps"])
    if kind.endswith("dense"):
        return x + swiglu(u, w["w_gate"], w["w_up"], w["w_down"])
    return x + routed(u, w, d) + swiglu(
        u, w["shared_gate"], w["shared_up"], w["shared_down"])


def embed(ids, emb):
    return jnp.take(emb, ids, axis=0)


def logits(x, top, d, round_fn=_id):
    h = rms_norm(x, top["final_norm"], d["norm_eps"])
    return jnp.einsum("...d,dv->...v", h, top["head"], precision=HI)
