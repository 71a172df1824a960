"""``last_logits_only`` held to the full call, for every decoder the
engine serves (tests/test_models.py, tests/test_mixed_layer_lm.py)."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from mlcomp_tpu.models.generation import init_cache
from mlcomp_tpu.ops.quant import quant_kernel_interception


def _chunked_prefill(model, variables, ids, chunk, l_buf, intercept, **kw):
    """A prompt in chunks against one cache, as the engine's chunk
    programs call the model; per chunk (logits, what the call sowed)."""
    b, s = ids.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    cache = init_cache(model, b, l_buf)
    out = []
    for lo in range(0, s, chunk):
        with (quant_kernel_interception(fold_norms=True) if intercept
              else contextlib.nullcontext()):
            logits, upd = model.apply(
                {**variables, "cache": cache}, ids[:, lo:lo + chunk],
                decode=True, positions=positions[:, lo:lo + chunk],
                kv_mask=jnp.ones((b, l_buf), bool),
                mutable=["cache", "counters"], **kw)
        cache = upd["cache"]
        out.append((logits, upd))
    return out


def assert_last_logits_only_is_the_last_row(model, variables, ids, chunk,
                                            l_buf, intercept=False,
                                            rtol=1e-5, atol=1e-5):
    """Under the keyword a chunk returns (B, 1, V), the full call's
    last row; cache and sown counters are the full call's to the bit
    (the layers see the whole chunk either way)."""
    full, narrow = (
        _chunked_prefill(model, variables, ids, chunk, l_buf, intercept, **kw)
        for kw in ({}, {"last_logits_only": True}))
    b, vocab = ids.shape[0], model.vocab_size
    for (lf, uf), (ln, un) in zip(full, narrow):
        assert lf.shape == (b, chunk, vocab) and ln.shape == (b, 1, vocab)
        assert ln.dtype == lf.dtype
        np.testing.assert_allclose(np.asarray(ln), np.asarray(lf[:, -1:]),
                                   rtol=rtol, atol=atol)
        assert (jax.tree_util.tree_structure(un)
                == jax.tree_util.tree_structure(uf))
        jax.tree_util.tree_map(
            lambda x, y: np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y)), uf, un)
    return full, narrow
