"""Serving's comparison: the served tokens against the plain reference.

Runs after the window has closed and the service's state is freed.  The
reference regenerates each layer's weights from the seed, rounds them to
the precision the configuration states (``rounding.weights``), and runs
every sampled request's prompt together with its served tokens through
the full forward pass in float32 — once, teacher-forced, no cache.  Two
numbers come out:

- ``max_logit_gap``: the widest gap by which a served (greedy) token's
  reference logit lies below the reference's best at that position;
- ``mean_abs_logprob_err``: the mean distance between the log-probability
  the service reported for a served token and the reference's.

With ``control=True`` the same prompts and tokens also go through the
forward pass at the nearest precision below each one the configuration
states (``quant.BELOW``): once with the weights a step down
(``control.*``: int4 under int8, int8 under bfloat16), and, where the
configuration states one for the keys and values, once with the stated
weights and the keys and values a step down (``control_kv.*``).  A
control's numbers are the gap of the token it puts first, and the
distance of its log-probabilities from the reference's.

The architecture is the file the configuration's ``reference`` key
names.  Its layers need not be alike: the comparison walks
``layer_kinds(d)`` with a Python index and compiles one program a KIND
(the layer's index is traced inside it), so a model of 24 like layers
compiles one layer program and one with a dense first layer, two kinds
of attention and routed experts compiles a handful.  Shapes may differ
between kinds, never within one.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import cells
from benchmark import weights as W
from benchmark.reference import quant
from benchmark.reference.quant import quantize_leaves


def passes_of(cfg: Dict[str, Any], control: bool):
    """(log name, weights' qmax, rounding of keys and values) of the
    reference and, with ``control``, of each control."""
    weights = quant.stated(cfg, "weights")
    kv = quant.stated(cfg, "kv")
    stated = quant.weight_qmax(weights)
    out = [("", stated, None)]
    if control:
        out.append(("control",
                    quant.weight_qmax(quant.below("weights", weights)), None))
        if kv is not None:
            out.append(("control_kv", stated,
                        quant.kv_round(quant.below("kv", kv))))
    return tuple(out)


def serve_readings(cfg: Dict[str, Any], seed: int,
                   samples: List[Dict[str, Any]], pad_len: int,
                   control: bool = False) -> Dict[str, float]:
    M = cells.architecture(cfg)
    d = M.dims_of(cfg)
    axes = M.CONTRACT_AXES
    key = W.seed_key(seed)
    n = len(samples)
    blk = M.rows_per_block(d, pad_len)
    n_pad = -(-n // blk) * blk
    toks = np.zeros((n_pad, pad_len), np.int32)
    for r, s in enumerate(samples):
        seq = list(s["ids"]) + list(s["out"])
        toks[r, :len(seq)] = seq
    pos = jnp.broadcast_to(jnp.arange(pad_len, dtype=jnp.int32),
                           (blk, pad_len))
    passes = passes_of(cfg, control)

    @jax.jit
    def embed_all(key, toks):
        top = M.top_weights(key, d, jnp.bfloat16)
        return tuple(
            M.embed(toks, quantize_leaves({"emb": top["emb"]}, q, axes)["emb"])
            for _, q, _ in passes
        )

    def layers_of(kind):
        """The program for every layer of one kind."""

        @jax.jit
        def layer_all(key, i, xs):
            w = M.layer_weights(key, i, d, jnp.bfloat16, kind)
            out = []
            for (_, q, kv), x in zip(passes, xs):
                wq = quantize_leaves(w, q, axes)
                kw = {} if kv is None else {"kv_fn": kv}
                xb = x.reshape(n_pad // blk, blk, pad_len, d["hidden"])
                y = jax.lax.map(
                    lambda b: M.layer(b, wq, pos, d, kind, **kw), xb
                )
                out.append(y.reshape(x.shape))
            return tuple(out)

        return layer_all

    @jax.jit
    def head_all(key, hs):
        top = M.top_weights(key, d, jnp.bfloat16)
        return tuple(
            M.logits(h, quantize_leaves(top, q, axes), d)
            for (_, q, _), h in zip(passes, hs)
        )

    xs = embed_all(key, jnp.asarray(toks))
    programs = {}
    for i, kind in enumerate(M.layer_kinds(d)):
        if kind not in programs:
            programs[kind] = layers_of(kind)
        xs = programs[kind](key, jnp.int32(i), xs)

    gap = lp_err = 0.0
    c_gap = [0.0] * (len(passes) - 1)
    c_lp_err = [0.0] * (len(passes) - 1)
    count = 0
    width = max(len(s["out"]) for s in samples)
    for r, s in enumerate(samples):
        p, m = len(s["ids"]), len(s["out"])
        idx = np.minimum(np.arange(p - 1, p - 1 + width), pad_len - 1)
        lgs = head_all(key, tuple(x[r, idx] for x in xs))
        ref = np.asarray(lgs[0])[:m]
        tok = np.asarray(s["out"], np.int64)
        rows = np.arange(m)
        ref_lp = ref - _logsumexp(ref)
        gap = max(gap, float(np.max(ref.max(-1) - ref[rows, tok])))
        lp_err += float(np.sum(np.abs(
            np.asarray(s["logprobs"], np.float64) - ref_lp[rows, tok]
        )))
        count += m
        for c in range(len(passes) - 1):
            ctl = np.asarray(lgs[c + 1])[:m]
            first = ctl.argmax(-1)
            c_gap[c] = max(c_gap[c],
                           float(np.max(ref.max(-1) - ref[rows, first])))
            ctl_lp = ctl - _logsumexp(ctl)
            c_lp_err[c] += float(np.sum(np.abs(
                ctl_lp[rows, tok] - ref_lp[rows, tok]
            )))
    out = {
        "max_logit_gap": gap,
        "mean_abs_logprob_err": lp_err / max(count, 1),
        "tokens_compared": float(count),
    }
    for c, (name, _, _) in enumerate(passes[1:]):
        out[f"{name}.max_logit_gap"] = c_gap[c]
        out[f"{name}.mean_abs_logprob_err"] = c_lp_err[c] / max(count, 1)
    return out


def _logsumexp(x: np.ndarray) -> np.ndarray:
    m = x.max(-1, keepdims=True)
    return m + np.log(np.exp(x - m).sum(-1, keepdims=True))
