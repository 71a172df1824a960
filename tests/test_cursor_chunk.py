"""The per-row-cursor contract of models/transformer.py
(``cache_cursor``): one token a row, each row at its own depth.

Rows at different depths stepped together through the per-row-cursor
path must produce the logits and the cache each row produces alone
through the one-cursor (``cache_index``) path — for both cache modes
(bf16 and int8 KV) and both head layouts — and a chunk (s > 1) under a
cursor is refused: chunks run under the one ``cache_index``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlcomp_tpu.models import create_model
from mlcomp_tpu.models.generation import init_cache
from mlcomp_tpu.train.state import init_model

L_BUF = 32
DEPTHS = (6, 4)     # prompt tokens a row holds before the steps
STEPS = 3


def _setup(kv_quant, kv_heads=None):
    model = create_model({
        "name": "transformer_lm", "vocab_size": 64, "hidden": 64,
        "layers": 2, "heads": 2, "mlp_dim": 128, "dtype": "float32",
        "kv_quant": kv_quant,
        **({"kv_heads": kv_heads} if kv_heads else {}),
    })
    rs = np.random.RandomState(3)
    prompts = [jnp.asarray(rs.randint(1, 64, (1, n))) for n in DEPTHS]
    toks = jnp.asarray(rs.randint(1, 64, (len(DEPTHS), STEPS)))
    params, _ = init_model(
        model, {"x": prompts[0]}, jax.random.PRNGKey(0)
    )
    return model, params, prompts, toks


def _prefill(model, params, prompt):
    n = prompt.shape[1]
    _, upd = model.apply(
        {"params": params, "cache": init_cache(model, 1, L_BUF)}, prompt,
        decode=True, positions=jnp.arange(n, dtype=jnp.int32)[None],
        mutable=["cache"],
    )
    return upd["cache"]


@pytest.mark.parametrize("kv_quant", [False, True])
@pytest.mark.parametrize("kv_heads", [None, 1])
def test_cursor_step_matches_one_cursor_path(kv_quant, kv_heads):
    model, params, prompts, toks = _setup(kv_quant, kv_heads)
    alone = [_prefill(model, params, p) for p in prompts]

    # each row alone, one token at a time under the one cache_index
    ref_logits, ref_caches = [], []
    for r, cache in enumerate(alone):
        row = []
        for j in range(STEPS):
            lg, upd = model.apply(
                {"params": params, "cache": cache}, toks[r:r + 1, j:j + 1],
                decode=True,
                positions=jnp.full((1, 1), DEPTHS[r] + j, jnp.int32),
                mutable=["cache"],
            )
            row.append(lg[0, 0])
            cache = upd["cache"]
        ref_logits.append(jnp.stack(row))
        ref_caches.append(cache)

    # the rows together, each at its own cursor
    cache = jax.tree.map(
        lambda a, b: a if a.ndim == 0 else jnp.concatenate([a, b]), *alone
    )
    cursors = jnp.asarray(DEPTHS, jnp.int32)
    got = []
    for j in range(STEPS):
        lg, upd = model.apply(
            {"params": params, "cache": cache}, toks[:, j:j + 1],
            decode=True, positions=(cursors + j)[:, None],
            cache_cursor=cursors + j, mutable=["cache"],
        )
        got.append(lg[:, 0])
        cache = upd["cache"]
    got = jnp.stack(got, axis=1)

    for r in range(len(DEPTHS)):
        np.testing.assert_allclose(
            np.asarray(got[r]), np.asarray(ref_logits[r]),
            atol=1e-4, rtol=1e-4,
        )
        # the same slots hold the same K and V afterwards
        for together, own in zip(
            jax.tree.leaves(cache), jax.tree.leaves(ref_caches[r])
        ):
            if together.ndim == 0:
                continue  # cache_index: unused under cursors
            np.testing.assert_allclose(
                np.asarray(together[r], np.float32),
                np.asarray(own[0], np.float32), atol=1e-5,
            )


def test_cursor_with_a_chunk_raises():
    model, params, prompts, toks = _setup(kv_quant=True)
    cache = _prefill(model, params, prompts[0])
    with pytest.raises(ValueError, match="single-token"):
        model.apply(
            {"params": params, "cache": cache}, toks[:1, :2], decode=True,
            positions=DEPTHS[0] + jnp.arange(2, dtype=jnp.int32)[None],
            cache_cursor=jnp.asarray(DEPTHS[:1], jnp.int32),
            mutable=["cache"],
        )
