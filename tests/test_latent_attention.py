"""Latent attention (``models/latent_attention.py``,
``ops/pallas/latent_attention.py``): attention in the latent space (the
up-projections absorbed), in its tiled chunk form and in its
single-token kernel, against plain attention on keys and values
expanded a head; per-row cursors; the kernel in interpret mode against
XLA at ragged windows, its in-place append, rows without a window.  The
layer's tests run twice: as Kimi-Linear has it (nothing rotated, one
query projection, no scale) and as LongCat-Flash has it (the shared key
and each head's ``q_pe`` rotated by position, a low-rank query, both
rank scales)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlcomp_tpu.models.latent_attention import (
    COUNTS,
    LatentAttention,
    latent_chunk_attention,
)
from mlcomp_tpu.models.transformer import RopeSpec
from mlcomp_tpu.ops.pallas.latent_attention import (
    BLOCK,
    block_of,
    blocks_fetched,
    buffer_len,
    latent_decode,
)

HIDDEN, HEADS, NOPE, ROPE, VD, DC = 48, 4, 16, 8, 16, 32
Q_RANK, Q_SCALE, KV_SCALE, THETA = 24, 2.0, 1.5 ** 0.5, 1e7
HI = jax.lax.Precision.HIGHEST
VARIANTS = ["kimi", "longcat"]


def _layer(variant="kimi"):
    more = {} if variant == "kimi" else {
        "rope": RopeSpec(base=THETA), "q_rank": Q_RANK, "q_scale": Q_SCALE,
        "kv_scale": KV_SCALE}
    return LatentAttention(HIDDEN, HEADS, jnp.float32, NOPE, ROPE, VD, DC,
                           **more)


def _turned(x, pos):
    """``x`` (B, S, ..., ROPE) rotated by position: column j pairs with
    j + ROPE / 2 (``apply_rope_spec``'s convention)."""
    half = ROPE // 2
    inv = THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * inv
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _plain(params, x, valid=None, pos=None):
    """Keys and values expanded for every token and head, softmax over
    the whole sequence: the layer as it is defined.  With ``q_a`` among
    the parameters the query is low-rank, q_pe and k_pe are rotated by
    ``pos`` and the two scales apply."""
    b, s, _ = x.shape
    norm = lambda a, w: a * jax.lax.rsqrt(  # noqa: E731
        jnp.mean(a * a, -1, keepdims=True) + 1e-6) * w
    h = norm(x, params["RMSNorm_0"]["scale"])
    kv = jnp.einsum("bsd,dc->bsc", h, params["kv_a"]["kernel"], precision=HI)
    c, k_pe = norm(kv[..., :DC], params["kv_norm"]), kv[..., DC:]
    if "q_a" in params:
        low = norm(jnp.einsum("bsd,dr->bsr", h, params["q_a"]["kernel"],
                              precision=HI), params["q_norm"])
        q = Q_SCALE * jnp.einsum("bsr,rhk->bshk", low,
                                 params["q_b"]["kernel"], precision=HI)
        q = jnp.concatenate([q[..., :NOPE], _turned(q[..., NOPE:], pos)], -1)
        c, k_pe = c * KV_SCALE, _turned(k_pe, pos)
    else:
        q = jnp.einsum("bsd,dhk->bshk", h, params["q"]["kernel"],
                       precision=HI)
    up = jnp.einsum("bsc,chk->bshk", c, params["kv_b"], precision=HI)
    k = jnp.concatenate([
        up[..., :NOPE],
        jnp.broadcast_to(k_pe[:, :, None], (b, s, HEADS, ROPE))], -1)
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


def _seeded(variant):
    layer = _layer(variant)
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 40, HIDDEN))
    pos = jnp.broadcast_to(jnp.arange(40), (3, 40))
    params = layer.init(jax.random.PRNGKey(1), x, pos)["params"]
    query = {"q": {"kernel": (HIDDEN, HEADS, NOPE + ROPE)}} \
        if variant == "kimi" else {
            "q_a": {"kernel": (HIDDEN, Q_RANK)}, "q_norm": (Q_RANK,),
            "q_b": {"kernel": (Q_RANK, HEADS, NOPE + ROPE)}}
    assert {k: jax.tree.map(jnp.shape, v) for k, v in params.items()} == {
        "RMSNorm_0": {"scale": (HIDDEN,)}, **query,
        "kv_a": {"kernel": (HIDDEN, DC + ROPE)}, "kv_norm": (DC,),
        "kv_b": (DC, HEADS, NOPE + VD), "out": {"kernel": (HEADS, VD, HIDDEN)}}
    params = {**params, "kv_norm": 1.0 + 0.3 * jnp.cos(jnp.arange(DC))}
    if variant != "kimi":
        params["q_norm"] = 1.0 + 0.3 * jnp.sin(jnp.arange(Q_RANK))
    return params, x, pos


@pytest.fixture(scope="module")
def seeded():
    return _seeded("kimi")


@pytest.fixture(scope="module", params=VARIANTS)
def either(request):
    return (request.param,) + _seeded(request.param)


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


def test_the_absorbed_tiled_form_is_plain_attention(either):
    variant, params, x, pos = either
    got = _layer(variant).apply({"params": params}, x, pos)
    np.testing.assert_allclose(got, _plain(params, x, pos=pos), atol=2e-5)
    if variant == "longcat":
        # and each of the three mechanisms is seen
        for undone in ({"rope": None}, {"q_scale": 1.0}, {"kv_scale": 1.0}):
            other = _layer(variant).clone(**undone).apply(
                {"params": params}, x, pos)
            assert np.abs(np.asarray(other - got)).max() > 1e-2, undone


def test_without_the_new_fields_the_layer_is_what_it_was(seeded):
    """Nothing rotates, the query is one projection and no scale is in
    the program: the neutral values of the new fields (a rotation of no
    dimension, scales of 1) give the same bits, and the traced program
    has no cosine."""
    params, x, pos = seeded
    got = _layer().apply({"params": params}, x, pos)
    neutral = _layer().clone(rope=RopeSpec(rotary_dim=0), q_scale=1.0,
                             kv_scale=1.0)
    np.testing.assert_array_equal(
        got, neutral.apply({"params": params}, x, pos))
    text = str(jax.make_jaxpr(
        lambda p: _layer().apply({"params": p}, x, pos))(params))
    assert " cos " not in text and " sin " not in text


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


def test_chunks_then_steps_through_the_cache_with_left_pads(either):
    """Row r is left-padded by 3 r slots; two chunks, then single-token
    steps at per-row cursors: plain attention over the row's own
    tokens, each at its own position (a rotated key is rotated before
    its row is cached, in the chunk form and in the step alike)."""
    variant, params, x, _ = either
    layer = _layer(variant)
    pads = np.array([0, 3, 6])
    width = 40 + 6
    padded = np.array(jax.random.normal(jax.random.PRNGKey(9),
                                        (3, width, HIDDEN)))
    for r, p in enumerate(pads):
        padded[r, p:p + 40] = x[r]
    padded = jnp.asarray(padded)
    l_buf = width + 5
    kv_mask = jnp.asarray(np.arange(l_buf)[None] >= pads[:, None])
    # a row's token j sits at slot pad + j and has position j
    pos = jnp.asarray(np.maximum(np.arange(width)[None] - pads[:, None], 0))
    want = np.asarray(_plain(params, padded, kv_mask[:, :width], pos))
    cache = _zero_cache(layer, 3, l_buf)
    outs, n_prefill = [], 30
    for lo, hi in ((0, 7), (7, n_prefill)):
        y, upd = layer.apply(
            {"params": params, "cache": cache}, padded[:, lo:hi],
            pos[:, lo:hi], decode=True, kv_mask=kv_mask,
            mutable=["cache", "counters"])
        cache = upd["cache"]
        outs.append(y)
    valid_in_chunk = sum(n_prefill - max(7, p) for p in pads)
    np.testing.assert_allclose(upd["counters"]["latent"],
                               [0, 0, valid_in_chunk, 1])
    for t in range(n_prefill, width):
        y, upd = layer.apply(
            {"params": params, "cache": cache}, padded[:, t:t + 1],
            pos[:, t:t + 1], decode=True, kv_mask=kv_mask,
            cache_cursor=jnp.full((3,), t), mutable=["cache", "counters"])
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
