"""A fixture architecture whose layers are of two kinds with different
shapes: the repo's own ``moe_lm`` at a tiny size, a ``DecoderLayer``
(dense SwiGLU MLP) then a ``MoELayer`` (a float32 softmax router, the
top ``k`` of its experts renormalised, two-matrix GELU experts; at
inference no token is dropped).  Registered by ``test_correct.py``, not
a configuration: no cell runs it.

It has the names ``benchmark/README.md`` asks of a file under
``reference/``; what the two kinds share (attention, the dense layer,
the top) is InternLM2's, imported from there.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from benchmark import weights as W
from benchmark.reference import internlm2 as base
from benchmark.reference.internlm2 import (  # noqa: F401  (the interface)
    TOP_LEAVES,
    embed,
    logits,
    program_top,
    rows_per_block,
    top_weights,
)

HI = base.HI
# expert matrices are stacked (expert, in, out); the router is float32
# in the program whatever the weights are, so it has no entry
CONTRACT_AXES = {**base.CONTRACT_AXES, "experts_w1": (1,), "experts_w2": (1,)}
MOE_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "router",
              "experts_w1", "experts_w2")


def dims_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    return {**base.dims_of(cfg), "experts": int(cfg["n_experts"]),
            "top_k": int(cfg["experts_per_token"]),
            "moe_every": int(cfg["moe_every"])}


def layer_kinds(d: Dict[str, Any]) -> List[str]:
    return ["moe" if (i + 1) % d["moe_every"] == 0 else "dense"
            for i in range(d["layers"])]


def layer_weights(key, i, d, dtype, kind) -> Dict[str, Any]:
    if kind == "dense":
        return base.layer_weights(key, i, d, dtype)
    h, e, f = d["hidden"], d["experts"], d["mlp"]
    shapes = {**base._shapes(d), "router": ((h, e), h),
              "experts_w1": ((e, h, f), h), "experts_w2": ((e, f, h), f)}
    return W.layer_leaves(key, i, {n: shapes[n] for n in MOE_LEAVES}, dtype)


def layer_key(i: int, d: Dict[str, Any]) -> str:
    """Flax numbers the layers of each class apart."""
    kinds = layer_kinds(d)
    cls = {"dense": "DecoderLayer", "moe": "MoELayer"}[kinds[i]]
    return f"{cls}_{kinds[:i].count(kinds[i])}"


def program_layer(w: Dict[str, Any], kind) -> Dict[str, Any]:
    if kind == "dense":
        return base.program_layer(w)
    return {
        "attn": {
            "RMSNorm_0": {"scale": w["attn_norm"]},
            "q": {"kernel": w["wq"]},
            "k": {"kernel": w["wk"]},
            "v": {"kernel": w["wv"]},
            "out": {"kernel": w["wo"]},
        },
        "RMSNorm_0": {"scale": w["mlp_norm"]},
        "moe": {"router": {"kernel": w["router"]},
                "experts_w1": w["experts_w1"],
                "experts_w2": w["experts_w2"]},
    }


def layer(x, w, positions, d, kind, round_fn=base._id, kv_fn=base._id):
    if kind == "dense":
        return base.layer(x, w, positions, d, round_fn=round_fn, kv_fn=kv_fn)
    eps, theta = d["norm_eps"], d["rope_base"]
    h = base.rms_norm(x, w["attn_norm"], eps)
    q = jnp.einsum("bsd,dhk->bshk", h, w["wq"], precision=HI)
    k = jnp.einsum("bsd,dhk->bshk", h, w["wk"], precision=HI)
    v = jnp.einsum("bsd,dhk->bshk", h, w["wv"], precision=HI)
    a = base.attention(base.rope(q, positions, theta),
                       kv_fn(base.rope(k, positions, theta)), kv_fn(v))
    x = x + jnp.einsum("bshk,hkd->bsd", a, w["wo"], precision=HI)
    h = base.rms_norm(x, w["mlp_norm"], eps)
    probs = jax.nn.softmax(
        jnp.einsum("bsd,de->bse", h, w["router"], precision=HI), axis=-1)
    top, idx = jax.lax.top_k(probs, d["top_k"])
    gates = top / jnp.sum(top, axis=-1, keepdims=True)
    weight = jnp.sum(
        jax.nn.one_hot(idx, d["experts"], dtype=jnp.float32)
        * gates[..., None], axis=-2)                         # (b, s, e)
    act = jax.nn.gelu(
        jnp.einsum("bsd,edf->bsef", h, w["experts_w1"], precision=HI))
    out = jnp.einsum("bsef,efd->bsed", act, w["experts_w2"], precision=HI)
    return x + jnp.einsum("bsed,bse->bsd", out, weight, precision=HI)
