"""Latent attention (``models/latent_attention.py``,
``ops/pallas/latent_attention.py``): attention in the latent space (the
up-projections absorbed), in its tiled chunk form and in its
single-token kernel, against plain attention on keys and values
expanded a head; per-row cursors; the kernel in interpret mode against
XLA at ragged windows, its in-place append, rows without a window."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlcomp_tpu.models.latent_attention import (
    COUNTS,
    LatentAttention,
    latent_chunk_attention,
)
from mlcomp_tpu.ops.pallas.latent_attention import (
    BLOCK,
    block_of,
    blocks_fetched,
    buffer_len,
    latent_decode,
)

HIDDEN, HEADS, NOPE, ROPE, VD, DC = 48, 4, 16, 8, 16, 32
HI = jax.lax.Precision.HIGHEST


def _layer():
    return LatentAttention(HIDDEN, HEADS, jnp.float32, NOPE, ROPE, VD, DC)


def _plain(params, x, valid=None):
    """Keys and values expanded for every token and head, softmax over
    the whole sequence: the layer as it is defined."""
    b, s, _ = x.shape
    norm = lambda a, w: a * jax.lax.rsqrt(  # noqa: E731
        jnp.mean(a * a, -1, keepdims=True) + 1e-6) * w
    h = norm(x, params["RMSNorm_0"]["scale"])
    q = jnp.einsum("bsd,dhk->bshk", h, params["q"]["kernel"], precision=HI)
    kv = jnp.einsum("bsd,dc->bsc", h, params["kv_a"]["kernel"], precision=HI)
    up = jnp.einsum("bsc,chk->bshk", norm(kv[..., :DC], params["kv_norm"]),
                    params["kv_b"], precision=HI)
    k = jnp.concatenate([
        up[..., :NOPE],
        jnp.broadcast_to(kv[:, :, None, DC:], (b, s, HEADS, ROPE))], -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) \
        / jnp.sqrt(float(NOPE + ROPE))
    t = jnp.arange(s)
    seen = (t[:, None] >= t[None, :])[None, None]
    if valid is not None:
        seen = seen & valid[:, None, None, :]
    p = jax.nn.softmax(jnp.where(seen, scores, -1e30), -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, up[..., NOPE:], precision=HI)
    return x + jnp.einsum("bqhd,hdo->bqo", o, params["out"]["kernel"],
                          precision=HI)


@pytest.fixture(scope="module")
def seeded():
    layer = _layer()
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 40, HIDDEN))
    pos = jnp.broadcast_to(jnp.arange(40), (3, 40))
    params = layer.init(jax.random.PRNGKey(1), x, pos)["params"]
    assert {k: jax.tree.map(jnp.shape, v) for k, v in params.items()} == {
        "RMSNorm_0": {"scale": (HIDDEN,)},
        "q": {"kernel": (HIDDEN, HEADS, NOPE + ROPE)},
        "kv_a": {"kernel": (HIDDEN, DC + ROPE)}, "kv_norm": (DC,),
        "kv_b": (DC, HEADS, NOPE + VD), "out": {"kernel": (HEADS, VD, HIDDEN)}}
    params = {**params, "kv_norm": 1.0 + 0.3 * jnp.cos(jnp.arange(DC))}
    return params, x, pos


def _zero_cache(layer, b, length):
    shapes = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), jnp.zeros((b, length, HIDDEN)),
        jnp.zeros((b, length), jnp.int32), decode=True))
    assert shapes["counters"]["latent"].shape == (len(COUNTS.entries),)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        shapes["cache"])


def test_the_buffer_is_whole_blocks_and_whole_lanes():
    assert (block_of(9729), buffer_len(9729)) == (BLOCK, 10240)
    assert (block_of(40), buffer_len(40)) == (48, 48)
    assert (block_of(1100), buffer_len(1100)) == (512, 1536)
    cache = _zero_cache(_layer(), 2, 50)
    # 32 + 8 numbers a token, in a leaf of whole 128-lane tiles
    assert cache["cached_latent"].shape == (2, 64, 128)
    np.testing.assert_array_equal(
        blocks_fetched(jnp.array([0, 5, 600, 9]), jnp.array([513, 5, 601, 3]),
                       512), [2, 0, 1, 0])


def test_the_absorbed_tiled_form_is_plain_attention(seeded):
    params, x, pos = seeded
    got = _layer().apply({"params": params}, x, pos)
    np.testing.assert_allclose(got, _plain(params, x), atol=2e-5)


def test_query_tiles_and_key_blocks_cover_every_pair(monkeypatch):
    """Several query tiles over several key blocks, a first slot that
    is not a block's, invalid slots in front: against one softmax over
    the whole buffer."""
    import mlcomp_tpu.models.latent_attention as mod

    monkeypatch.setattr(mod, "Q_TILE", 8)
    b, s, h, w, dc, first = 2, 20, 3, 40, 32, 1027
    length = buffer_len(first + s)
    assert length // block_of(length) == 3
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    q = jax.random.normal(ks[0], (b, s, h, w)) * 0.3
    lat = jax.random.normal(ks[1], (b, length, w))
    valid = jnp.arange(length)[None] >= jnp.array([[0], [700]])
    got = latent_chunk_attention(q, lat, first, valid, dc, HI)
    scores = jnp.einsum("bqhw,bkw->bhqk", q, lat, precision=HI)
    seen = valid[:, None, None, :] & (
        jnp.arange(length)[None, :] <= (first + jnp.arange(s))[:, None])
    p = jax.nn.softmax(jnp.where(seen, scores, -1e30), -1)
    want = jnp.einsum("bhqk,bkc->bqhc", p, lat[..., :dc], precision=HI)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_chunks_then_steps_through_the_cache_with_left_pads(seeded):
    """Row r is left-padded by 3 r slots; two chunks, then single-token
    steps at per-row cursors: plain attention over the row's own
    tokens."""
    params, x, pos = seeded
    layer = _layer()
    pads = np.array([0, 3, 6])
    width = 40 + 6
    padded = np.array(jax.random.normal(jax.random.PRNGKey(9),
                                        (3, width, HIDDEN)))
    for r, p in enumerate(pads):
        padded[r, p:p + 40] = x[r]
    padded = jnp.asarray(padded)
    l_buf = width + 5
    kv_mask = jnp.asarray(np.arange(l_buf)[None] >= pads[:, None])
    want = np.asarray(_plain(params, padded, kv_mask[:, :width]))
    cache = _zero_cache(layer, 3, l_buf)
    outs, n_prefill = [], 30
    for lo, hi in ((0, 7), (7, n_prefill)):
        y, upd = layer.apply(
            {"params": params, "cache": cache}, padded[:, lo:hi], None,
            decode=True, kv_mask=kv_mask, mutable=["cache", "counters"])
        cache = upd["cache"]
        outs.append(y)
    valid_in_chunk = sum(n_prefill - max(7, p) for p in pads)
    np.testing.assert_allclose(upd["counters"]["latent"],
                               [0, 0, valid_in_chunk, 1])
    for t in range(n_prefill, width):
        y, upd = layer.apply(
            {"params": params, "cache": cache}, padded[:, t:t + 1], None,
            decode=True, kv_mask=kv_mask, cache_cursor=jnp.full((3,), t),
            mutable=["cache", "counters"])
        cache = upd["cache"]
        outs.append(y)
    got = np.asarray(jnp.concatenate(outs, 1))
    for r, p in enumerate(pads):
        np.testing.assert_allclose(got[r, p:], want[r, p:], atol=2e-5)
    # the last step attended each row's window, from one fetched block
    block_bytes = 64 * 128 * 4
    np.testing.assert_allclose(
        upd["counters"]["latent"],
        [sum(width - p for p in pads), 3 * block_bytes, 0, 1])


def _xla_decode(q, new, cache, start, stop, dc):
    b, length, _ = cache.shape
    slots = jnp.arange(length)[None, :]
    put = (slots == (stop - 1)[:, None]) & (stop > start)[:, None]
    cache = jnp.where(put[..., None], new[:, None, :], cache)
    seen = (slots >= start[:, None]) & (slots < stop[:, None])
    scores = jnp.einsum("bhw,blw->bhl", q, cache, precision=HI)
    p = jax.nn.softmax(jnp.where(seen[:, None], scores, -1e30), -1)
    p = jnp.where(seen[:, None], p, 0.0)
    return jnp.einsum("bhl,blc->bhc", p, cache[..., :dc], precision=HI), cache


@pytest.mark.parametrize("length", [40, 1100], ids=["one_block", "blocks"])
def test_latent_decode_against_xla_at_ragged_windows(length):
    """Windows that start and stop anywhere, one of a single token, two
    empty (a row without a request): outputs, the appended latent in
    place, and nothing else of the cache touched."""
    b, h, dc, w = 6, 4, 32, 128
    buf = buffer_len(length)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, w)) * 0.3
    new = jax.random.normal(ks[1], (b, w))
    cache = jax.random.normal(ks[2], (b, buf, w))
    start = jnp.array([0, 3, 5, 0, 17, buf])
    stop = jnp.array([length, 20, 5, 1, 33, length - 1])
    out, written = latent_decode(q, new, cache, start, stop, dc=dc)
    want, want_cache = _xla_decode(q, new, cache, start, stop, dc)
    np.testing.assert_allclose(out, want, atol=2e-5)
    np.testing.assert_array_equal(written, want_cache)
    assert not np.asarray(out[2]).any() and not np.asarray(out[5]).any()
    np.testing.assert_array_equal(written[2], cache[2])


def test_what_the_kernel_is_not_given_is_refused(seeded):
    params, x, pos = seeded
    with pytest.raises(ValueError, match="not whole blocks"):
        latent_decode(jnp.zeros((1, 4, 128)), jnp.zeros((1, 128)),
                      jnp.zeros((1, 520, 128)), jnp.zeros((1,), jnp.int32),
                      jnp.ones((1,), jnp.int32), dc=32)
    layer = _layer()
    with pytest.raises(ValueError, match="single-token"):
        layer.apply(
            {"params": params, "cache": _zero_cache(layer, 3, 50)},
            x[:, :2], None, decode=True,
            cache_cursor=jnp.zeros((3,), jnp.int32),
            mutable=["cache", "counters"])
