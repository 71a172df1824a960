"""Weight quantization as the configurations state it, written from the
statement and not from the program: symmetric, round to nearest,
``scale = absmax / qmax`` per output channel — the scale is constant
along the contraction axes, so it factors out of the product.

``fake_quant(w, axes, 127)`` is int8, the serving configuration's weight
type; ``fake_quant(w, axes, 7)`` is int4, its control (the nearest
precision below, the step that would tempt a later PR).  Which axes a
leaf's scale is constant along is the architecture's to say
(``CONTRACT_AXES`` in its file under ``reference/``).

``kv_round`` is the second serving control: the configuration keeps keys
and values in int8 with a scale per (token, kv head); the control rounds
them to int4 the same way.

What a configuration rounds to is its ``rounding`` mapping, read here
and nowhere else: ``{"weights": "int8", "kv": "int8"}`` for serving,
``{"operands": "bfloat16"}`` for training.  ``BELOW`` is the ladder of
controls: the nearest precision below each one a configuration can
state, the step that would tempt a later PR.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

BELOW = {
    "weights": {"bfloat16": "int8", "int8": "int4"},
    "kv": {"bfloat16": "int8", "int8": "int4"},
    "operands": {"bfloat16": "fp8"},
}
QMAX = {"int8": 127, "int4": 7}


def stated(cfg: Dict[str, Any], what: str) -> Optional[str]:
    """The precision a configuration states for ``what`` (``weights``,
    ``kv``, ``operands``); None where it states none."""
    if "rounding" not in cfg:
        raise SystemExit(
            'the configuration states no precision: add "rounding", e.g. '
            '{"weights": "int8", "kv": "int8"}'
        )
    name = cfg["rounding"].get(what)
    if name is not None and name not in BELOW[what]:
        raise SystemExit(
            f"rounding.{what} {name!r}: this yardstick knows "
            f"{sorted(BELOW[what])}"
        )
    return name


def below(what: str, name: str) -> str:
    return BELOW[what][name]


def weight_qmax(name: str) -> Optional[int]:
    """``quantize_leaves``' qmax for weights stated as ``name``; None for
    bfloat16, which the seeded weights are drawn in."""
    return QMAX.get(name)


def kv_round(name: str) -> Callable:
    """Keys or values (..., kv heads, head_dim) rounded to ``name``, one
    scale per (token, kv head): absmax over the head's dimension / qmax."""
    return lambda x: fake_quant(x, (-1,), QMAX[name])


def fake_quant(w, axes, qmax: int):
    w = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w), axis=axes, keepdims=True)
    scale = jnp.maximum(absmax, 1e-12) / float(qmax)
    return jnp.clip(jnp.round(w / scale), -qmax, qmax) * scale


def quantize_leaves(weights, qmax, contract_axes):
    """Every matrix leaf through ``fake_quant``; norm scales untouched.
    ``qmax`` None returns float32 copies."""
    out = {}
    for name, w in weights.items():
        if name in contract_axes and qmax is not None:
            out[name] = fake_quant(w, contract_axes[name], qmax)
        else:
            out[name] = w.astype(jnp.float32)
    return out


def operand_round(name: str) -> Callable:
    """The rounding of every product's operands, training's control."""
    return {"fp8": fp8}[name]


def fp8(x):
    """Round to float8 (e4m3) and back, scaled per tensor so that the
    largest magnitude sits at the format's 448, rounding in the forward
    pass only (a straight-through gradient): the control of bfloat16
    compute.  Without the scale and the straight-through pass, e4m3's
    range would flush every gradient to zero, which is a crash and no
    control."""
    x = x.astype(jnp.float32)
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = jax.lax.stop_gradient(scale)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)
