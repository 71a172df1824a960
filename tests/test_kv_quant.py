"""int8 KV-cache decode: kernel numerics + end-to-end generation parity.

Covers ops/pallas/decode_attention.py (flash-decode over an int8 cache,
interpret mode on CPU) and the ``kv_quant`` wiring in
models/transformer.py / models/moe.py.  The serving rationale and
measured numbers live in the kernel docstring; here we pin correctness:

- kernel vs a dequantize-then-softmax XLA reference (same quantized
  inputs, so the comparison isolates the KERNEL, not the quantization);
- per-row [start, stop) windows including a one-slot and an EMPTY window
  (empty rows must produce exact zeros, the online-softmax guard);
- GQA grouping, dh < 128 zero-padding, and the lane-rounded buffer;
- end-to-end: prefill logits BIT-equal to the bf16-cache path (prefill
  attends fresh K/V in both), decode-step logits within int8 noise, for
  transformer_lm, moe_lm, GQA, and ragged left-padded prompts.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlcomp_tpu.models import create_model
from mlcomp_tpu.models.generation import generate, init_cache
from mlcomp_tpu.ops.pallas.decode_attention import (
    decode_attention,
    quantize_kv,
)


def _reference(q, k8, ks, v8, vs, start, stop, scale):
    b, h, dh = q.shape
    h_kv, l_buf = k8.shape[1], k8.shape[2]
    rep = h // h_kv
    kd = k8.astype(jnp.float32) * ks[..., None]
    vd = v8.astype(jnp.float32) * vs[..., None]
    qg = q.astype(jnp.float32).reshape(b, h_kv, rep, dh)
    logits = jnp.einsum("bhgd,bhld->bhgl", qg, kd) * scale
    slots = jnp.arange(l_buf)
    mask = (slots[None] >= start[:, None]) & (slots[None] < stop[:, None])
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    # exact-zero rows for empty windows, like the kernel guard
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(mask[:, None, None, :].any(-1, keepdims=True), p, 0.0)
    return jnp.einsum("bhgl,bhld->bhgd", p, vd).reshape(b, h, dh)


@pytest.mark.parametrize("h,h_kv,dh", [(8, 8, 128), (8, 2, 128), (4, 1, 64)])
def test_decode_kernel_matches_reference(h, h_kv, dh):
    rng = np.random.default_rng(0)
    b, l_buf = 4, 256
    dhp = max(dh, 128)
    q = jnp.asarray(rng.normal(size=(b, h, dhp)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h_kv, l_buf, dhp)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h_kv, l_buf, dhp)), jnp.float32)
    if dhp != dh:  # emulate the model's zero-padding of small head dims
        zero = jnp.zeros_like(q[..., dh:])
        q = q.at[..., dh:].set(zero)
        k = k.at[..., dh:].set(0.0)
        v = v.at[..., dh:].set(0.0)
    k8, ks = quantize_kv(k)
    v8, vs = quantize_kv(v)
    # windows: full, interior, ONE slot, EMPTY
    start = jnp.asarray([0, 37, 40, 50], jnp.int32)
    stop = jnp.asarray([256, 130, 41, 50], jnp.int32)
    scale = 1.0 / (dh**0.5)
    out = decode_attention(
        q, k8, ks[:, :, None, :], v8, vs[:, :, None, :],
        kv_start=start, kv_stop=stop, scale=scale,
    )
    ref = _reference(q, k8, ks, v8, vs, start, stop, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2)
    assert np.abs(np.asarray(out[3])).max() == 0.0  # empty window: zeros


@pytest.mark.parametrize("h,h_kv,dh,s_q", [
    (8, 8, 128, 5), (8, 2, 128, 9), (4, 1, 64, 3), (4, 4, 128, 1),
])
def test_chunk_kernel_matches_reference(h, h_kv, dh, s_q):
    """Multi-query kernel vs a dequant reference with per-query causal
    stops: query j attends [start, stop0 + j)."""
    from mlcomp_tpu.ops.pallas.decode_attention import (
        decode_attention_chunk,
    )

    rng = np.random.default_rng(1)
    b, l_buf = 3, 256
    dhp = max(dh, 128)
    q = jnp.asarray(rng.normal(size=(b, s_q, h, dhp)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h_kv, l_buf, dhp)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h_kv, l_buf, dhp)), jnp.float32)
    if dhp != dh:
        q = q.at[..., dh:].set(0.0)
        k = k.at[..., dh:].set(0.0)
        v = v.at[..., dh:].set(0.0)
    k8, ks = quantize_kv(k)
    v8, vs = quantize_kv(v)
    start = jnp.asarray([0, 17, 40], jnp.int32)
    stop0 = jnp.asarray([200, 60, 41], jnp.int32)  # incl. a 1-slot row
    scale = 1.0 / (dh**0.5)
    out = decode_attention_chunk(
        q, k8, ks[:, :, None, :], v8, vs[:, :, None, :],
        kv_start=start, kv_stop0=stop0, scale=scale,
    )
    # reference: S independent single-token calls at growing stops
    refs = []
    for j in range(s_q):
        refs.append(_reference(
            q[:, j], k8, ks, v8, vs, start, stop0 + j, scale
        ))
    ref = jnp.stack(refs, axis=1)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-2
    )


def test_chunk_kernel_agrees_with_single_token_kernel():
    """S == 1 chunk against decode_attention: the same arithmetic core.
    A row whose window touches every lane block of the walk's granule
    is moved and attended whole, in the chunk kernel's block (by
    default the walk's granule): the same partition of the online
    softmax, so BIT-equal.  A row whose trip is trimmed to the blocks
    its window touches (PR 36) reduces over fewer columns than the
    chunk kernel's block, and a walk handed a thinner granule over
    more blocks: the partition is no longer the same by construction,
    and both are held to float rounding."""
    from mlcomp_tpu.ops.pallas.decode_attention import (
        decode_attention_chunk,
    )

    rng = np.random.default_rng(2)
    b, h, h_kv, dh, l_buf = 2, 8, 4, 128, 1024
    q = jnp.asarray(rng.normal(size=(b, h, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h_kv, l_buf, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h_kv, l_buf, dh)), jnp.float32)
    k8, ks = quantize_kv(k)
    v8, vs = quantize_kv(v)
    start = jnp.asarray([0, 11], jnp.int32)
    stop = jnp.asarray([897, 64], jnp.int32)   # all 8 blocks; one block
    operands = (k8, ks[:, :, None, :], v8, vs[:, :, None, :])
    c = decode_attention_chunk(
        q[:, None], *operands, kv_start=start, kv_stop0=stop,
    )[:, 0]
    default = decode_attention(q, *operands, kv_start=start, kv_stop=stop)
    np.testing.assert_array_equal(np.asarray(default[0]), np.asarray(c[0]))
    np.testing.assert_allclose(
        np.asarray(default[1]), np.asarray(c[1]), atol=1e-5
    )
    thin = decode_attention(
        q, *operands, kv_start=start, kv_stop=stop, block_kv=128,
    )
    assert not np.array_equal(np.asarray(thin), np.asarray(c))
    np.testing.assert_allclose(np.asarray(thin), np.asarray(c), atol=1e-5)


# rows of (start, stop) against an L-slot buffer; G is the kernel's
# granule there (640 at 8 KV heads and L 2560, 512 at 16; 2176 at 4 KV
# heads and L 13056), B = 128 the lane block a trip is trimmed to
_RAGGED = {
    "empty_between_live": lambda G, L: [
        (3, 700), (L, 41), (G, G + 1), (90, 90), (500, 20), (2 * G, L),
    ],
    "one_token": lambda G, L: [
        (0, 1), (G - 1, G), (G, G + 1), (L - 1, L), (777, 778),
    ],
    "granule_edges": lambda G, L: [
        (G, 3 * G), (G - 1, 3 * G + 1), (G + 1, 3 * G - 1),
        (0, G), (2 * G, 2 * G + 1), (L - G, L),
    ],
    "whole_buffer": lambda G, L: [(0, L), (0, L)],
    # what trimming a trip to its window's lane blocks creates (PR 36):
    # a window inside one lane block: the buffer's first, an inner one,
    # a granule's last, the buffer's last
    "one_lane_block": lambda G, L: [
        (5, 100), (G + 130, G + 250), (2 * G - 127, 2 * G - 1),
        (L - 100, L - 3), (G + 128, G + 256),
    ],
    # a window whose first block is its granule's block 0, whose last
    # is its granule's last, both, and neither
    "first_and_last_block": lambda G, L: [
        (G, G + 129), (G + 1, 2 * G), (2 * G - 129, 2 * G),
        (G + 128, 2 * G - 128), (G + 127, 2 * G - 127),
    ],
    # windows that end exactly on a block's edge and on a granule's,
    # and start on one
    "block_and_granule_ends": lambda G, L: [
        (G + 5, G + 256), (7, G), (G - 128, G), (3, 2 * G),
        (G + 256, 2 * G + 128), (128, 129),
    ],
    # longer than a granule, both edges trimmed (whole granules between)
    "both_edges_trimmed": lambda G, L: [
        (G - 200, 2 * G + 300), (G - 1, 2 * G + 1), (G - 128, 3 * G + 128),
        (2 * G - 257, L - G + 140),
    ],
    # a window layer's read at long contexts: the last 4,096 tokens
    "window_4096": lambda G, L: [
        (hi - 4096, hi)
        for hi in (L - 756, 4097, L, 3 * G, 4500)
    ],
}
# (id, query heads, KV heads, buffer): the InternLM2 cells' geometry,
# GQA and MHA, and SmallThinker's (groups of 7 over 4 KV heads, a
# granule of 17 lane blocks)
_RAGGED_PARAMS = [
    pytest.param(h, h_kv, l_buf, case, id=f"{geo}-{case}")
    for geo, h, h_kv, l_buf, cases in (
        ("gqa", 16, 8, 2560, sorted(set(_RAGGED) - {"window_4096"})),
        ("mha", 16, 16, 2560, sorted(set(_RAGGED) - {"window_4096"})),
        ("hkv4_l13056", 28, 4, 13056, ["both_edges_trimmed", "window_4096"]),
    )
    for case in cases
]
# the cells' own geometries (the append and the poison test run these)
_RAGGED_GQA = [p for p in _RAGGED_PARAMS if p.id.startswith(("gqa", "hkv4"))]


def _ragged_case(h, h_kv, l_buf, case):
    from mlcomp_tpu.ops.pallas.decode_attention import auto_block_kv

    granule = auto_block_kv(l_buf, h_kv, 128)
    assert granule <= l_buf // 4
    return granule, _RAGGED[case](granule, l_buf)


def _ragged_operands(h, h_kv, l_buf, win, seed):
    """q, int8 K and V with their scales as the cache stores them
    (bfloat16, (B, Hkv, L)), and the windows as arrays."""
    b, dh = len(win), 128
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, h, dh)), jnp.bfloat16)
    k8, ks = quantize_kv(
        jnp.asarray(rng.normal(size=(b, h_kv, l_buf, dh)), jnp.float32)
    )
    v8, vs = quantize_kv(
        jnp.asarray(rng.normal(size=(b, h_kv, l_buf, dh)), jnp.float32)
    )
    start, stop = (jnp.asarray(x, jnp.int32) for x in zip(*win))
    return (q, k8, ks.astype(jnp.bfloat16), v8, vs.astype(jnp.bfloat16),
            start, stop)


@pytest.mark.parametrize("h,h_kv,l_buf,case", _RAGGED_PARAMS)
def test_decode_kernel_walks_ragged_windows(h, h_kv, l_buf, case):
    """The kernel walks each row's own granules: every column of every
    window is attended (float reference over the same int8 bytes), a
    row with an empty window returns exact zeros whatever its bounds,
    and what its neighbours return does not depend on it."""
    _, win = _ragged_case(h, h_kv, l_buf, case)
    q, k8, ks, v8, vs, start, stop = _ragged_operands(
        h, h_kv, l_buf, win, seed=3
    )
    operands = (k8, ks[:, :, None, :], v8, vs[:, :, None, :])
    scale = 1.0 / 128**0.5
    out = np.asarray(decode_attention(
        q, *operands, kv_start=start, kv_stop=stop, scale=scale,
    ).astype(jnp.float32))
    ref = np.asarray(_reference(
        q, k8, ks.astype(jnp.float32), v8, vs.astype(jnp.float32),
        start, stop, scale,
    ))
    np.testing.assert_allclose(out, ref, atol=2e-2)
    empty = np.asarray(start >= stop)
    assert (out[empty] == 0.0).all()
    if empty.any():
        # the same live rows beside rows that DO hold a window
        filled = np.asarray(decode_attention(
            q, *operands, kv_start=jnp.where(empty, 0, start),
            kv_stop=jnp.where(empty, l_buf, stop), scale=scale,
        ).astype(jnp.float32))
        np.testing.assert_array_equal(out[~empty], filled[~empty])


def _fetched_columns(win, l_buf, granule):
    """(B, L) bool: the columns the walk moves for each window, by the
    kernel module's own account of a trip (``trip_fetch``)."""
    from mlcomp_tpu.ops.pallas.decode_attention import trip_fetch

    moved = np.zeros((len(win), l_buf), bool)
    for r, (lo, hi) in enumerate(win):
        lo, hi = max(lo, 0), min(hi, l_buf)
        for g in range(l_buf // granule) if hi > lo else ():
            if lo < (g + 1) * granule and hi > g * granule:
                col, width = trip_fetch(lo, hi, g, granule, np)
                moved[r, col:col + width] = True
    return moved


@pytest.mark.parametrize("h,h_kv,l_buf,case", _RAGGED_GQA)
def test_decode_kernel_reads_no_column_it_did_not_fetch(h, h_kv, l_buf, case):
    """Every column the walk does NOT move (``trip_fetch``'s account of
    its trips: the lane blocks a window touches, rounded up to a rung)
    holds NaN scales in HBM, as a slot of VMEM scratch may hold from an
    earlier trip: outputs stay finite and equal to the float reference.
    0 x NaN would pass the mask (``p * vs``), so this holds only if a
    trip copies no more than the account says and attends no more than
    it copied; the walk that moved whole granules fails it.  The
    account covers every live column."""
    granule, win = _ragged_case(h, h_kv, l_buf, case)
    q, k8, ks, v8, vs, start, stop = _ragged_operands(
        h, h_kv, l_buf, win, seed=5
    )
    moved = _fetched_columns(win, l_buf, granule)
    cols = np.arange(l_buf)
    for r, (lo, hi) in enumerate(win):
        assert moved[r][(cols >= lo) & (cols < hi)].all()
    keep = jnp.asarray(moved)[:, None, :]
    poisoned = [
        jnp.where(keep, x, jnp.nan)[:, :, None, :] for x in (ks, vs)
    ]
    scale = 1.0 / 128**0.5
    out = np.asarray(decode_attention(
        q, k8, poisoned[0], v8, poisoned[1], kv_start=start, kv_stop=stop,
        scale=scale,
    ).astype(jnp.float32))
    assert np.isfinite(out).all()
    ref = np.asarray(_reference(
        q, k8, ks.astype(jnp.float32), v8, vs.astype(jnp.float32),
        start, stop, scale,
    ))
    np.testing.assert_allclose(out, ref, atol=2e-2)


def test_fetch_ladder_and_the_tokens_a_walk_moves():
    """``fetch_ladder``: lane multiples from one block to the granule,
    at most five.  ``kv_tokens_fetched`` (the engine's counter) against
    a brute-force count: a granule a window touches, the smallest rung
    that covers the lane blocks it touches there."""
    from mlcomp_tpu.ops.pallas.decode_attention import (
        fetch_ladder,
        kv_tokens_fetched,
        trip_fetch,
    )

    assert fetch_ladder(640) == (128, 256, 384, 640)
    assert fetch_ladder(2176) == (128, 256, 512, 1024, 2176)
    assert fetch_ladder(384) == (128, 256, 384)
    assert fetch_ladder(128) == (128,)
    for granule in range(128, 4096 + 1, 128):
        ladder = fetch_ladder(granule)
        assert ladder[0] == 128 and ladder[-1] == granule
        assert len(ladder) <= 5 and list(ladder) == sorted(set(ladder))
        assert all(w % 128 == 0 for w in ladder)
    rng = np.random.default_rng(36)
    for granule, l_buf in ((640, 2560), (384, 1152), (2176, 13056),
                           (1024, 1024)):
        ladder = fetch_ladder(granule)
        lo = rng.integers(-5, l_buf + 5, 400)
        hi = rng.integers(-5, l_buf + 200, 400)
        lo[:40], hi[:40] = hi[:40] - 128, hi[:40]      # thin windows
        want = []
        for a, z in zip(lo, hi):
            a, z = max(a, 0), min(z, l_buf)
            total = 0
            for g in range(l_buf // granule):
                blocks = [
                    blk for blk in range(g * granule // 128,
                                         (g + 1) * granule // 128)
                    if blk * 128 < z and (blk + 1) * 128 > a and z > a
                ]
                if blocks:
                    need = 128 * (blocks[-1] - blocks[0] + 1)
                    rung = min(w for w in ladder if w >= need)
                    total += rung
                    # the trip's fetch covers them, inside the granule
                    col, width = trip_fetch(a, z, g, granule, np)
                    assert width == rung and col % 128 == 0
                    assert g * granule <= col <= blocks[0] * 128
                    assert (blocks[-1] + 1) * 128 <= col + width \
                        <= (g + 1) * granule
            want.append(total)
        got = kv_tokens_fetched(lo, hi, l_buf, granule)
        np.testing.assert_array_equal(got, np.asarray(want))


def _append_case(h, h_kv, dh, l_buf, sdt, cursors, starts, seed=1):
    """Operands of one append call, and what the write it replaced
    leaves behind (``conftest.loop_write_kv``)."""
    from conftest import loop_write_kv

    b = len(cursors)
    dhp = max(dh, 128)
    rng = np.random.default_rng(seed)

    def normal(*shape):
        x = jnp.asarray(rng.normal(size=shape), jnp.float32)
        # the model zero-pads a small head dim to a lane multiple
        return x.at[..., dh:].set(0.0) if dhp != dh else x

    q = normal(b, h, dhp).astype(jnp.bfloat16)
    k8, ks = quantize_kv(normal(b, h_kv, l_buf, dhp))
    v8, vs = quantize_kv(normal(b, h_kv, l_buf, dhp))
    ks, vs = (x.astype(sdt)[:, :, None, :] for x in (ks, vs))
    caches = (k8, ks, v8, vs)
    new = (*quantize_kv(normal(b, h_kv, dhp)),
           *quantize_kv(normal(b, h_kv, dhp)))
    cur = jnp.asarray(cursors, jnp.int32)
    windows = dict(
        kv_start=jnp.asarray(starts, jnp.int32), kv_stop=cur + 1,
        scale=1.0 / dh**0.5,
    )
    return q, caches, new, windows, loop_write_kv(caches, new, cur)


# (cursors, window starts) against an L-slot buffer walked in granules
# of G: the new token goes to the cursor and the window ends behind it
_APPEND = {
    # the first slot, both sides of an int8 tile's edge and of a
    # granule's, and the buffer's last slots
    "cursors": lambda G, L: (
        [0, 31, 32, G - 1, G, L - 2, L - 1], [0] * 7),
    # a raised kv_start: the window's first granule is not the buffer's
    "window": lambda G, L: (
        [G + 5, 2 * G - 1, L - 2, 40], [G + 1, G + 3, L - 100, 40]),
    # rows 1, 3 and 4 hold no window: start past the cursor, or at L as
    # the engine hands a slot without a request
    "empty_rows": lambda G, L: (
        [77, 90, G, 5, L - 1, G + 7], [0, 91, 3, L, L, G]),
}


@pytest.mark.parametrize("case", sorted(_APPEND))
@pytest.mark.parametrize("sdt", [jnp.bfloat16, jnp.float32],
                         ids=["bf16_scales", "f32_scales"])
@pytest.mark.parametrize("h,h_kv,dh", [(16, 8, 128), (4, 2, 64)],
                         ids=["hkv8_dh128", "padded_dh64"])
def test_decode_kernel_appends_in_place(h, h_kv, dh, sdt, case):
    """``append``: the kernel writes the row's new token at its cursor
    and attends it.  Against the write it replaced (a row loop of
    update-slices, a select over the scale caches, then the plain
    kernel): the output and all four buffers bit-equal; a row with an
    empty window returns zeros and keeps every byte it had."""
    from mlcomp_tpu.ops.pallas.decode_attention import auto_block_kv

    l_buf, block_kv = 1280, 640
    granule = min(block_kv, auto_block_kv(l_buf, h_kv, 128))
    assert granule == 640
    cursors, starts = _APPEND[case](granule, l_buf)
    q, caches, new, windows, want = _append_case(
        h, h_kv, dh, l_buf, sdt, cursors, starts
    )
    out, *got = decode_attention(
        q, *caches, block_kv=block_kv, append=new, **windows
    )
    ref = decode_attention(q, *want, block_kv=block_kv, **windows)
    bits = lambda x: np.asarray(x.astype(jnp.float32))
    np.testing.assert_array_equal(bits(out), bits(ref))
    live = np.asarray(windows["kv_start"] < windows["kv_stop"])
    assert live.all() == (case != "empty_rows")
    assert (bits(out)[~live] == 0.0).all()
    for new_buf, old_write, old_buf in zip(got, want, caches):
        assert new_buf.shape == old_buf.shape
        assert new_buf.dtype == old_buf.dtype
        np.testing.assert_array_equal(
            bits(new_buf)[live], bits(old_write)[live]
        )
        np.testing.assert_array_equal(
            bits(new_buf)[~live], bits(old_buf)[~live]
        )


@pytest.mark.parametrize("h,h_kv,l_buf,case", _RAGGED_GQA)
def test_decode_kernel_appends_at_ragged_windows(h, h_kv, l_buf, case):
    """``append`` over the windows of
    ``test_decode_kernel_walks_ragged_windows``, the new token at each
    window's last column: a trimmed trip patches the token relative to
    where its fetch starts and writes the tile back where the whole-
    granule trip wrote it.  Output and all four buffers bit-equal to
    the write it replaced in every live row (so a column of the granule
    that was never fetched is untouched too), an empty row's bytes
    untouched."""
    _, win = _ragged_case(h, h_kv, l_buf, case)
    q, caches, new, windows, want = _append_case(
        h, h_kv, 128, l_buf, jnp.bfloat16,
        [hi - 1 for _, hi in win], [lo for lo, _ in win],
    )
    out, *got = decode_attention(q, *caches, append=new, **windows)
    ref = decode_attention(q, *want, **windows)
    bits = lambda x: np.asarray(x.astype(jnp.float32))
    np.testing.assert_array_equal(bits(out), bits(ref))
    live = np.asarray(windows["kv_start"] < windows["kv_stop"])
    assert (bits(out)[~live] == 0.0).all()
    for new_buf, old_write, old_buf in zip(got, want, caches):
        np.testing.assert_array_equal(
            bits(new_buf)[live], bits(old_write)[live]
        )
        np.testing.assert_array_equal(
            bits(new_buf)[~live], bits(old_buf)[~live]
        )


def test_decode_kernel_append_rejects_bad_shapes():
    q, caches, new, windows, _ = _append_case(
        4, 2, 128, 256, jnp.bfloat16, [3, 4], [0, 0]
    )
    with pytest.raises(ValueError, match="append"):
        decode_attention(
            q, *caches, append=(new[0][:, :1], *new[1:]), **windows
        )


@pytest.mark.parametrize("shape", [(2, 1, 2), (1, 2, 2), (4, 1, 1)],
                         ids=["dp2_tp2", "fsdp2_tp2", "dp4"])
def test_sharded_decode_attention_appends(shape):
    """Under a mesh the append rides the shard_map island: rows over
    the data axis, KV heads over ``tp``, each device writing its own
    shard of the caches; bit-equal to the single-device call."""
    from jax.sharding import Mesh
    from mlcomp_tpu.ops.pallas.decode_attention import (
        sharded_decode_attention,
    )

    mesh = Mesh(
        np.asarray(jax.devices()[:4]).reshape(shape), ("dp", "fsdp", "tp")
    )
    q, caches, new, windows, _ = _append_case(
        8, 4, 128, 256, jnp.bfloat16, [0, 40, 128, 254], [0, 41, 100, 0]
    )
    want = decode_attention(q, *caches, append=new, **windows)
    got = jax.jit(lambda *a: sharded_decode_attention(
        *a[:5], mesh, append=a[5:], **windows
    ))(q, *caches, *new)
    plain = sharded_decode_attention(
        q, *want[1:], mesh, **windows
    )
    bits = lambda x: np.asarray(x.astype(jnp.float32))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(bits(g), bits(w))
    np.testing.assert_array_equal(bits(plain), bits(want[0]))


def test_chunk_kernel_tiles_wide_chunks():
    """Chunks wider than one kernel tile no longer raise (the pre-
    ISSUE-13 NotImplementedError): they run as query-TILED sweeps —
    shape-correct, and each tile bit-identical to calling the kernel
    on that tile with the position-offset stop."""
    from mlcomp_tpu.ops.pallas.decode_attention import (
        CHUNK_MAX_SQ,
        decode_attention_chunk,
    )

    b, h, dh, l_buf = 1, 4, 128, 256
    s = CHUNK_MAX_SQ + 1
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, s, h, dh)), jnp.float32)
    k8 = jnp.asarray(rng.integers(-127, 128, (b, h, l_buf, dh)), jnp.int8)
    sc = jnp.asarray(rng.random((b, h, 1, l_buf)), jnp.float32)
    stop0 = jnp.asarray([l_buf - s + 1], jnp.int32)
    wide = decode_attention_chunk(
        q, k8, sc, k8, sc, kv_stop0=stop0, interpret=True
    )
    assert wide.shape == (b, s, h, dh)
    head = decode_attention_chunk(
        q[:, :CHUNK_MAX_SQ], k8, sc, k8, sc, kv_stop0=stop0,
        interpret=True,
    )
    tail = decode_attention_chunk(
        q[:, CHUNK_MAX_SQ:], k8, sc, k8, sc,
        kv_stop0=stop0 + CHUNK_MAX_SQ, interpret=True,
    )
    assert (np.asarray(head) == np.asarray(wide)[:, :CHUNK_MAX_SQ]).all()
    assert (np.asarray(tail) == np.asarray(wide)[:, CHUNK_MAX_SQ:]).all()


@pytest.mark.parametrize("window", [None, 48])
def test_a_chunk_of_many_tiles_loops_over_the_one_kernel(window):
    """More query tiles than are written out run as one kernel call in
    a loop over the tiles (plus a remainder tile): every tile is bit-
    identical to the kernel called on it with the position-offset
    stop, with and without a window."""
    from mlcomp_tpu.ops.pallas.decode_attention import (
        CHUNK_MAX_SQ,
        CHUNK_UNROLLED_TILES,
        decode_attention_chunk,
    )

    b, h, dh, l_buf = 1, 4, 128, 512
    n_tiles = CHUNK_UNROLLED_TILES + 2
    s = n_tiles * CHUNK_MAX_SQ + 5
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((b, s, h, dh)), jnp.float32)
    k8 = jnp.asarray(rng.integers(-127, 128, (b, 2, l_buf, dh)), jnp.int8)
    sc = jnp.asarray(rng.random((b, 2, 1, l_buf)), jnp.float32)
    start = jnp.asarray([7], jnp.int32)
    stop0 = jnp.asarray([l_buf - s + 1], jnp.int32)
    kw = dict(kv_start=start, window=window, interpret=True)
    wide = np.asarray(jax.jit(lambda q: decode_attention_chunk(
        q, k8, sc, k8, sc, kv_stop0=stop0, **kw))(q))
    assert wide.shape == (b, s, h, dh)
    for o in (0, CHUNK_MAX_SQ, (n_tiles - 1) * CHUNK_MAX_SQ,
              n_tiles * CHUNK_MAX_SQ):
        one = decode_attention_chunk(
            q[:, o:o + CHUNK_MAX_SQ], k8, sc, k8, sc, kv_stop0=stop0 + o,
            **kw)
        assert (np.asarray(one) == wide[:, o:o + CHUNK_MAX_SQ]).all()


def test_decode_kernel_rejects_bad_scale_shape():
    q = jnp.zeros((1, 4, 128))
    k8 = jnp.zeros((1, 4, 128, 128), jnp.int8)
    ks = jnp.zeros((1, 4, 128), jnp.float32)  # missing the singleton
    with pytest.raises(ValueError, match="scales"):
        decode_attention(q, k8, ks, k8, ks)


def _step_logits(model, variables, prompt, budget=16):
    """Prefill then one decode step; returns (prefill logits, step logits)."""
    b, s = prompt.shape
    cache = init_cache(model, b, budget)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    logits, upd = model.apply(
        {**variables, "cache": cache}, prompt, decode=True, positions=pos,
        mutable=["cache"],
    )
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    step, _ = model.apply(
        {**variables, "cache": upd["cache"]}, tok[:, None], decode=True,
        positions=jnp.full((b, 1), s, jnp.int32), mutable=["cache"],
    )
    return np.asarray(logits), np.asarray(step[:, 0])


@pytest.mark.parametrize(
    "name,extra",
    [
        ("transformer_lm", {}),
        ("transformer_lm", {"heads": 4, "kv_heads": 2}),
        ("moe_lm", {"n_experts": 4, "moe_every": 2}),
    ],
)
def test_kv_quant_decode_matches_bf16(name, extra):
    cfg = {"vocab_size": 64, "hidden": 64, "layers": 2, "heads": 4, **extra}
    m_bf = create_model({"name": name, **cfg})
    m_q = create_model({"name": name, **cfg, "kv_quant": True})
    rng = jax.random.PRNGKey(0)
    prompt = jax.random.randint(rng, (2, 7), 1, 64)
    variables = m_bf.init(rng, jnp.zeros((2, 16), jnp.int32))

    pre_bf, step_bf = _step_logits(m_bf, variables, prompt)
    pre_q, step_q = _step_logits(m_q, variables, prompt)
    # prefill never reads the quantized cache: bit-equal
    np.testing.assert_array_equal(pre_bf, pre_q)
    # the decode step reads int8 K/V: within quantization noise
    np.testing.assert_allclose(step_bf, step_q, atol=0.15)


def test_kv_quant_generate_ragged_and_eos():
    cfg = dict(vocab_size=64, hidden=64, layers=1, heads=4)
    m_bf = create_model({"name": "transformer_lm", **cfg})
    m_q = create_model({"name": "transformer_lm", **cfg, "kv_quant": True})
    rng = jax.random.PRNGKey(1)
    prompt = jax.random.randint(rng, (2, 6), 1, 64)
    pm = jnp.array([[False, False, True, True, True, True], [True] * 6])
    variables = m_bf.init(rng, jnp.zeros((2, 12), jnp.int32))
    out_bf = generate(m_bf, variables, prompt, 5, prompt_mask=pm)
    out_q = generate(m_q, variables, prompt, 5, prompt_mask=pm)
    assert out_q.shape == out_bf.shape == (2, 11)
    # random-init greedy argmax can flip on near-ties; require the bulk
    # of tokens to agree rather than bit-equality
    agree = float((out_bf[:, 6:] == out_q[:, 6:]).mean())
    assert agree >= 0.6, f"ragged int8 decode diverged: agreement {agree}"


def test_kv_quant_cache_is_int8():
    m_q = create_model(
        {"name": "transformer_lm", "vocab_size": 64, "hidden": 64,
         "layers": 1, "heads": 4, "kv_quant": True}
    )
    cache = init_cache(m_q, 2, 20)
    leaves = jax.tree.leaves(cache)
    dtypes = {str(x.dtype) for x in leaves}
    assert "int8" in dtypes
    kq = cache["DecoderLayer_0"]["attn"]["cached_key_q"]
    assert kq.dtype == jnp.int8
    assert kq.shape[2] % 128 == 0  # lane-rounded buffer


def test_buffer_length_picker_prefers_fat_blocks():
    """pick_buffer_len must never hand the kernel a divisor-free length:
    2176 = 128*17 would force 17 thin grid steps; the picker pads to the
    next fat-block length instead (r4 profiler finding)."""
    from mlcomp_tpu.ops.pallas.decode_attention import (
        auto_block_kv,
        pick_buffer_len,
    )

    from mlcomp_tpu.ops.pallas.decode_attention import KV_BLOCK_BUDGET

    # the serve-path shape that regressed: hkv=16, dh=128
    lpad = pick_buffer_len(2064, 16, 128)
    blk = auto_block_kv(lpad, 16, 128)
    assert lpad >= 2064 and lpad % 128 == 0
    assert blk >= 384, (lpad, blk)
    # the bench shape keeps its exact length (384 divides 2304 within
    # the ~2MB-per-step budget the late-r4 sweep picked)
    assert pick_buffer_len(2304, 16, 128) == 2304
    assert auto_block_kv(2304, 16, 128) == 384
    # short caches keep the whole buffer in one block
    s = pick_buffer_len(96, 4, 128)
    assert auto_block_kv(s, 4, 128) == s
    # budget respected: K+V block bytes never exceed it
    for l, h, d in ((16384, 8, 128), (4096, 32, 128), (512, 16, 256)):
        lp = pick_buffer_len(l, h, d)
        assert 2 * h * auto_block_kv(lp, h, d) * d <= KV_BLOCK_BUDGET
