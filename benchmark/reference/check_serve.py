"""Serving's comparison: the served tokens against the plain reference.

Runs after the window has closed and the service's state is freed.  The
reference regenerates each layer's weights from the seed, rounds them to
int8 as the configuration states, and runs every sampled request's
prompt together with its served tokens through the full forward pass in
float32 — once, teacher-forced, no cache.  Two numbers come out:

- ``max_logit_gap``: the widest gap by which a served (greedy) token's
  reference logit lies below the reference's best at that position;
- ``mean_abs_logprob_err``: the mean distance between the log-probability
  the service reported for a served token and the reference's.

With ``control=True`` the same prompts and tokens also go through the
forward pass at the nearest precision below each one the configuration
states: once with int4 weights (``control.*``), once with int8 weights
and the keys and values rounded to int4 (``control_kv.*``).  A control's
numbers are the gap of the token it puts first, and the distance of its
log-probabilities from the reference's.

The architecture (forward pass, seeded weights) is the file the
configuration's ``reference`` key names.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import cells
from benchmark import weights as W
from benchmark.reference.quant import kv_int4, quantize_leaves

# rows that go through a layer together: two 2304-token rows of float32
# scores per kv group fit the chip beside the reference's weights
ROWS_PER_BLOCK = 2

# (log name, weights' qmax, rounding of keys and values)
REFERENCE = ("", 127, None)
CONTROLS = (("control", 7, None), ("control_kv", 127, kv_int4))


def serve_readings(cfg: Dict[str, Any], seed: int,
                   samples: List[Dict[str, Any]], pad_len: int,
                   control: bool = False) -> Dict[str, float]:
    M = cells.architecture(cfg)
    d = M.dims_of(cfg)
    axes = M.CONTRACT_AXES
    eps = float(cfg["as_run"]["norm_eps"])
    theta = float(cfg["as_run"]["rope_base"])
    key = W.seed_key(seed)
    n = len(samples)
    blk = ROWS_PER_BLOCK
    n_pad = -(-n // blk) * blk
    toks = np.zeros((n_pad, pad_len), np.int32)
    for r, s in enumerate(samples):
        seq = list(s["ids"]) + list(s["out"])
        toks[r, :len(seq)] = seq
    pos = jnp.broadcast_to(jnp.arange(pad_len, dtype=jnp.int32),
                           (blk, pad_len))
    passes = (REFERENCE,) + (CONTROLS if control else ())

    @jax.jit
    def embed_all(key, toks):
        top = M.top_weights(key, d, jnp.bfloat16)
        return tuple(
            M.embed(toks, quantize_leaves({"emb": top["emb"]}, q, axes)["emb"])
            for _, q, _ in passes
        )

    @jax.jit
    def layer_all(key, i, xs):
        w = M.layer_weights(key, i, d, jnp.bfloat16)
        out = []
        for (_, q, kv), x in zip(passes, xs):
            wq = quantize_leaves(w, q, axes)
            kw = {} if kv is None else {"kv_fn": kv}
            xb = x.reshape(n_pad // blk, blk, pad_len, d["hidden"])
            y = jax.lax.map(
                lambda b: M.layer(b, wq, pos, eps, theta, **kw), xb
            )
            out.append(y.reshape(x.shape))
        return tuple(out)

    @jax.jit
    def head_all(key, hs):
        top = M.top_weights(key, d, jnp.bfloat16)
        return tuple(
            M.logits(h, quantize_leaves(top, q, axes), eps)
            for (_, q, _), h in zip(passes, hs)
        )

    xs = embed_all(key, jnp.asarray(toks))
    for i in range(d["layers"]):
        xs = layer_all(key, jnp.int32(i), xs)

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
