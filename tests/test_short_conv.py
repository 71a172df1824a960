"""The gated short convolution (``models/short_conv.py``): the three
forms of the one function agree with a plain convolution over the whole
sequence at float32 on seeded weights and tiny widths; a chunk cut at
any boundary, and through left pads, gives the same outputs and tail; a
row without a request keeps its tail; a fresh cache starts from zeros."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlcomp_tpu.models.short_conv import COUNTS, GatedShortConv
from mlcomp_tpu.models.transformer import rmsnorm

HIDDEN, TAPS, ROWS, LEN = 32, 3, 3, 70


def _layer():
    return GatedShortConv(HIDDEN, jnp.float32, taps=TAPS)


def _plain(params, x):
    """The layer's definition over a whole sequence, written out."""
    h = rmsnorm(x, params["RMSNorm_0"]["scale"], jnp.float32)
    gate_in, gate_out, value = jnp.split(h @ params["in"]["kernel"], 3, -1)
    u = jnp.pad(gate_in * value, ((0, 0), (TAPS - 1, 0), (0, 0)))
    c = sum(params["conv"][j] * u[:, j:j + x.shape[1]] for j in range(TAPS))
    return x + (gate_out * c) @ params["out"]["kernel"], u[:, -(TAPS - 1):]


@pytest.fixture(scope="module")
def seeded():
    """(params, inputs (3, 70, hidden), the plain convolution's output
    and the last two ``u``)."""
    layer = _layer()
    x = jax.random.normal(jax.random.PRNGKey(0), (ROWS, LEN, HIDDEN))
    params = layer.init(jax.random.PRNGKey(1), x, None)["params"]
    assert set(params) == {"RMSNorm_0", "in", "conv", "out"}
    assert params["conv"].shape == (TAPS, HIDDEN)
    with jax.default_matmul_precision("highest"):
        want, tail = _plain(params, x)
    return params, x, np.asarray(want), np.asarray(tail)


def _cache(rows):
    layer = _layer()
    return layer.init(
        jax.random.PRNGKey(1), jnp.zeros((rows, LEN, HIDDEN)), None,
        decode=True)["cache"]


def _chunk(params, cache, x, kv_mask=None):
    with jax.default_matmul_precision("highest"):
        out, upd = _layer().apply(
            {"params": params, "cache": cache}, x, None, decode=True,
            kv_mask=kv_mask, mutable=["cache", "counters"])
    return out, upd["cache"], upd["counters"]["conv"]


def test_the_form_without_a_cache_is_the_plain_convolution(seeded):
    params, x, want, _ = seeded
    with jax.default_matmul_precision("highest"):
        got = _layer().apply({"params": params}, x, None)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_a_fresh_cache_is_two_tokens_of_zeros_whatever_the_buffer():
    cache = _cache(5)
    assert {k: v.shape for k, v in cache.items()} == {
        "conv": (5, TAPS - 1, HIDDEN), "cache_index": ()}
    assert not np.asarray(cache["conv"]).any()


@pytest.mark.parametrize("width", [1, 2, 3, 64])
def test_chunks_across_boundaries_are_the_plain_convolution(seeded, width):
    """Chunks of 1 (shorter than the tail), 2 (the tail's length), 3 and
    64 tokens: outputs, the carried tail and the counts."""
    params, x, want, tail = seeded
    cache, got, tokens = _cache(ROWS), [], 0.0
    for lo in range(0, LEN, width):
        out, cache, counts = _chunk(params, cache, x[:, lo:lo + width])
        got.append(out)
        tokens += float(counts[COUNTS.names.index("chunk_tokens")])
        assert float(counts[COUNTS.names.index("layer_calls")]) == 1.0
        assert float(counts[COUNTS.names.index("state_rows")]) == 0.0
    np.testing.assert_allclose(jnp.concatenate(got, 1), want, atol=2e-5)
    np.testing.assert_allclose(cache["conv"], tail, atol=1e-6)
    assert int(cache["cache_index"]) == LEN and tokens == ROWS * LEN


@pytest.mark.parametrize("pad", [1, 5, 16, 21])
def test_left_pads_enter_as_zeros_of_u_not_of_x(seeded, pad):
    """A LEFT-padded row in chunks of 8: pads share a chunk with the
    first tokens, fill a chunk, or spill into a third.  The pads'
    inputs are NOT zero (an embedding is not), and the real positions
    still read what the unpadded sequence reads."""
    params, x, want, _ = seeded
    n = 27
    row = jnp.concatenate(
        [jnp.ones((ROWS, pad, HIDDEN)) * 3.0, x[:, :n]], axis=1)
    kv_mask = jnp.broadcast_to(jnp.arange(pad + n + 8) >= pad,
                               (ROWS, pad + n + 8))
    cache, got, tokens = _cache(ROWS), [], 0.0
    for lo in range(0, pad + n, 8):
        out, cache, counts = _chunk(params, cache, row[:, lo:lo + 8], kv_mask)
        got.append(out)
        tokens += float(counts[COUNTS.names.index("chunk_tokens")])
    got = jnp.concatenate(got, 1)[:, pad:]
    np.testing.assert_allclose(got, want[:, :n], atol=2e-5)
    assert tokens == ROWS * n
    # and without the mask the pads reach the first tokens
    out, _, _ = _chunk(params, _cache(ROWS), row[:, :pad + 4])
    assert np.abs(np.asarray(out[:, pad:]) - want[:, :4]).max() > 1e-2


def test_single_token_steps_follow_a_chunk_and_a_dead_row_keeps_its_tail(
        seeded):
    """A chunk of 40 tokens, then steps under per-row cursors: row 1
    holds no request (its ``kv_mask`` is all false) and its tail stays
    bit for bit; the live rows read the plain convolution."""
    params, x, want, _ = seeded
    _, cache, _ = _chunk(params, _cache(ROWS), x[:, :40])
    live = np.array([True, False, True])
    kv_mask = jnp.asarray(np.broadcast_to(live[:, None], (ROWS, LEN)))
    kept = np.asarray(cache["conv"][1])
    per_row = 2 * (TAPS - 1) * HIDDEN * 4
    for t in range(40, 46):
        with jax.default_matmul_precision("highest"):
            out, upd = _layer().apply(
                {"params": params, "cache": cache}, x[:, t:t + 1], None,
                decode=True, kv_mask=kv_mask,
                cache_cursor=jnp.full((ROWS,), t, jnp.int32),
                mutable=["cache", "counters"])
        cache = upd["cache"]
        np.testing.assert_allclose(out[live, 0], want[live, t], atol=2e-5)
        np.testing.assert_array_equal(cache["conv"][1], kept)
        np.testing.assert_array_equal(
            upd["counters"]["conv"], [2.0, 2.0 * per_row, 0.0, 1.0])
    # the cursor form moves no index: the chunk form's stays where it was
    assert int(cache["cache_index"]) == 40


def test_a_chunk_under_per_row_cursors_is_refused(seeded):
    params, x, _, _ = seeded
    with pytest.raises(ValueError, match="single-token"):
        _layer().apply(
            {"params": params, "cache": _cache(ROWS)}, x[:, :2], None,
            decode=True, cache_cursor=jnp.zeros((ROWS,), jnp.int32),
            mutable=["cache", "counters"])


def test_the_tail_is_kept_in_the_modules_dtype():
    layer = GatedShortConv(HIDDEN, jnp.bfloat16, taps=4)
    cache = layer.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 9, HIDDEN)), None,
        decode=True)["cache"]
    assert cache["conv"].shape == (2, 3, HIDDEN)
    assert cache["conv"].dtype == jnp.bfloat16
