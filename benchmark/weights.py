"""Seeded weights, made by the benchmark and by nobody else.

One function of (seed, layer, leaf) gives every weight of an InternLM2-
shaped decoder.  The harness builds the program's parameter tree from it
in ONE jitted call on the device; the plain reference regenerates the
same values layer by layer after the program's state is freed.  Neither
side takes a weight the other has made.

Leaves carry canonical names and the shapes the architecture defines:
projections as (hidden, heads, head_dim) / (heads, head_dim, hidden),
MLP matrices as (in, out), embedding (vocab, hidden), head (hidden,
vocab).  Matrices are N(0, 1/fan_in); norm scales are ones.  Values are
drawn in float32 and rounded to ``dtype`` (bfloat16 for serving, the
type the service is handed; float32 for training).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

LAYER_LEAVES = (
    "attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate", "w_up",
    "w_down",
)
TOP_LEAVES = ("emb", "final_norm", "head")


def dims_of(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Canonical sizes from a configuration file's published keys."""
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
    }


def leaf_shape(name: str, d: Dict[str, int]):
    """(shape, fan_in) of one canonical leaf; fan_in None = a norm scale."""
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
    }[name]


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number up to 2**32 and beyond."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )


def _leaf(key, name: str, d: Dict[str, int], dtype) -> jax.Array:
    shape, fan_in = leaf_shape(name, d)
    if fan_in is None:
        return jnp.ones(shape, jnp.float32)
    w = jax.random.normal(key, shape, jnp.float32) * (float(fan_in) ** -0.5)
    return w.astype(dtype)


def layer_weights(key, layer, d: Dict[str, int], dtype) -> Dict[str, Any]:
    """Layer ``layer``'s leaves (``layer`` may be a traced integer)."""
    lk = jax.random.fold_in(key, 1000 + layer)
    return {
        n: _leaf(jax.random.fold_in(lk, j), n, d, dtype)
        for j, n in enumerate(LAYER_LEAVES)
    }


def top_weights(key, d: Dict[str, int], dtype) -> Dict[str, Any]:
    tk = jax.random.fold_in(key, 1)
    return {
        n: _leaf(jax.random.fold_in(tk, j), n, d, dtype)
        for j, n in enumerate(TOP_LEAVES)
    }


def program_layer(w: Dict[str, Any]) -> Dict[str, Any]:
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


def layer_key(i: int) -> str:
    """Where layer ``i`` sits in the program's parameter tree."""
    return f"DecoderLayer_{i}"


def program_top(top: Dict[str, Any]) -> Dict[str, Any]:
    """The leaves outside the layers, in ``TransformerLM``'s layout."""
    return {
        "emb": {"embedding": top["emb"]},
        "RMSNorm_0": {"scale": top["final_norm"]},
        "lm_head": {"kernel": top["head"]},
    }


def program_params(seed: int, d: Dict[str, int], dtype,
                   shardings=None) -> Dict[str, Any]:
    """The whole parameter tree, on the device, in one jitted call;
    ``shardings`` (a tree like the result) places each leaf where a
    program on a mesh keeps it, so that no chip ever holds the whole."""

    def build(key):
        tree = program_top(top_weights(key, d, dtype))
        for i in range(d["layers"]):
            tree[layer_key(i)] = program_layer(
                layer_weights(key, i, d, dtype)
            )
        return tree

    return jax.jit(build, out_shardings=shardings)(seed_key(seed))


def check_layout(params, abstract) -> None:
    """The tree handed to the program must be the tree it would have
    made itself: same paths, same shapes."""
    ours = {
        jax.tree_util.keystr(p): tuple(x.shape)
        for p, x in jax.tree_util.tree_leaves_with_path(params)
    }
    theirs = {
        jax.tree_util.keystr(p): tuple(x.shape)
        for p, x in jax.tree_util.tree_leaves_with_path(abstract)
    }
    if ours != theirs:
        diff = sorted(set(ours.items()) ^ set(theirs.items()))[:8]
        raise RuntimeError(
            f"the program's parameter layout is not the benchmark's: {diff}"
        )
