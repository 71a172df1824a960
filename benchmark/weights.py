"""Seeded weights, made by the benchmark and by nobody else.

One function of (seed, layer, leaf) gives every weight of a model.  The
harness builds the program's parameter tree from it in ONE jitted call
on the device; the plain reference regenerates the same values layer by
layer after the program's state is freed.  Neither side takes a weight
the other has made.

What the leaves are called, their shapes and where the program keeps
them is the architecture's to say (its file under ``reference/``); this
file draws them.  A leaf is given as ``(shape, fan_in)``: a matrix is
N(0, 1/fan_in), drawn in float32 and rounded to ``dtype`` (bfloat16 for
serving, the type the service is handed; float32 for training); a leaf
whose ``fan_in`` is None is a norm scale, all ones.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

Shapes = Dict[str, Tuple[Tuple[int, ...], Any]]


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number up to 2**32 and beyond."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )


def _leaf(key, shape, fan_in, dtype) -> jax.Array:
    if fan_in is None:
        return jnp.ones(shape, jnp.float32)
    w = jax.random.normal(key, shape, jnp.float32) * (float(fan_in) ** -0.5)
    return w.astype(dtype)


def _leaves(key, shapes: Shapes, dtype) -> Dict[str, Any]:
    return {
        n: _leaf(jax.random.fold_in(key, j), shape, fan_in, dtype)
        for j, (n, (shape, fan_in)) in enumerate(shapes.items())
    }


def layer_leaves(key, layer, shapes: Shapes, dtype) -> Dict[str, Any]:
    """Layer ``layer``'s leaves (``layer`` may be a traced integer), in
    the order ``shapes`` names them: a leaf's values follow from the
    seed, the layer and its place in that order."""
    return _leaves(jax.random.fold_in(key, 1000 + layer), shapes, dtype)


def top_leaves(key, shapes: Shapes, dtype) -> Dict[str, Any]:
    """The leaves outside the layers."""
    return _leaves(jax.random.fold_in(key, 1), shapes, dtype)


def program_params(arch, seed: int, d: Dict[str, Any], dtype,
                   shardings=None) -> Dict[str, Any]:
    """The whole parameter tree of architecture ``arch`` in the
    program's layout, on the device, in one jitted call; ``shardings``
    (a tree like the result) places each leaf where a program on a mesh
    keeps it, so that no chip ever holds the whole."""
    kinds = arch.layer_kinds(d)

    def build(key):
        tree = arch.program_top(arch.top_weights(key, d, dtype))
        for i, kind in enumerate(kinds):
            tree[arch.layer_key(i, d)] = arch.program_layer(
                arch.layer_weights(key, i, d, dtype, kind), kind
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
