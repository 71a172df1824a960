"""Kimi Delta Attention (``models/kda.py``, ``ops/pallas/kda.py``): the
three forms of the one function agree with the per-token recurrence at
float32 on seeded weights and tiny widths; a chunk cut at any boundary,
and through left pads, gives the same state and outputs; the kernel in
interpret mode is the recurrence's step and leaves dead rows alone; the
convolution's tail carries across chunks and steps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlcomp_tpu.models.kda import (
    BLOCK,
    COUNTS,
    DECAY_RATES,
    KimiDeltaAttention,
    delta_chunks,
    short_conv,
)
from mlcomp_tpu.ops.pallas.kda import (
    heads_per_block,
    kda_step,
    state_bytes_moved,
)

HIDDEN, HEADS, DH = 48, 3, 16


def _recurrence(q, k, v, log_a, beta, state):
    """The layer's definition, a token at a time."""
    outs = []
    for t in range(q.shape[1]):
        state = jnp.exp(log_a[:, t])[..., None] * state
        u = beta[:, t][..., None] * (
            v[:, t] - jnp.einsum("bncd,bnc->bnd", state, k[:, t]))
        state = state + k[:, t][..., None] * u[..., None, :]
        outs.append(jnp.einsum("bncd,bnc->bnd", state, q[:, t]))
    return jnp.stack(outs, 1), state


def _drawn(b, s, n=HEADS, dh=DH, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    k = jax.random.normal(ks[1], (b, s, n, dh))
    return (
        jax.random.normal(ks[0], (b, s, n, dh)) * dh ** -0.5,
        k / jnp.linalg.norm(k, axis=-1, keepdims=True),
        jax.random.normal(ks[2], (b, s, n, dh)),
        -0.3 * jax.random.uniform(ks[3], (b, s, n, dh)),
        jax.random.uniform(ks[4], (b, s, n)),
        jax.random.normal(ks[5], (b, n, dh, dh)),
    )


@pytest.mark.parametrize("s", [1, 7, BLOCK, BLOCK + 1, 150])
def test_the_chunked_form_is_the_recurrence(s):
    """Whole blocks, a block short of tokens, several blocks and a
    carried state: outputs and the state after the chunk."""
    q, k, v, log_a, beta, state = _drawn(2, s)
    out, new = delta_chunks(q, k, v, log_a, beta, state)
    ref_out, ref_new = _recurrence(q, k, v, log_a, beta, state)
    np.testing.assert_allclose(out, ref_out, atol=5e-6)
    np.testing.assert_allclose(new, ref_new, atol=5e-6)


@pytest.mark.parametrize("live", [
    [True, False, True, True, False], [False] * 5, [True] * 5,
    [False, False, False, True, False]],
    ids=["mixed", "none", "all", "one"])
def test_kda_step_is_the_recurrences_step_and_leaves_dead_rows(live):
    q, k, v, log_a, beta, state = _drawn(5, 1, n=4)
    live = jnp.asarray(live)
    out, new = kda_step(q[:, 0], k[:, 0], v[:, 0], log_a[:, 0], beta[:, 0],
                        live, state)
    ref_out, ref_new = _recurrence(q, k, v, log_a, beta, state)
    sel = np.asarray(live)
    np.testing.assert_allclose(out[sel], ref_out[sel, 0], atol=5e-6)
    np.testing.assert_allclose(new[sel], ref_new[sel], atol=5e-6)
    # a row without a request: zeros out, its state bit for bit
    assert not np.asarray(out[~sel]).any()
    np.testing.assert_array_equal(new[~sel], state[~sel])


def test_the_steps_blocks_and_bytes():
    # 32 heads of 128 x 128 float32: 16 a block of 1 MiB, two a row
    assert heads_per_block(32, 128, 128) == 16
    assert heads_per_block(3, 16, 16) == 3
    assert state_bytes_moved(1, 32, 128, 128) == 32 * 128 * 128 * 4 * 2


def test_the_convolution_carries_its_tail():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 11, 6))
    taps = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    whole, tail = short_conv(x, jnp.zeros((2, 3, 6)), taps)
    padded = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
    np.testing.assert_allclose(
        whole, sum(taps[i] * padded[:, i:i + 11] for i in range(4)),
        atol=1e-6)
    np.testing.assert_array_equal(tail, x[:, -3:])
    # in pieces of 5, 1 and 5 tokens (a piece shorter than the tail)
    tail, got = jnp.zeros((2, 3, 6)), []
    for lo, hi in ((0, 5), (5, 6), (6, 11)):
        y, tail = short_conv(x[:, lo:hi], tail, taps)
        got.append(y)
    np.testing.assert_allclose(jnp.concatenate(got, 1), whole, atol=1e-6)
    np.testing.assert_array_equal(tail, x[:, -3:])


def _layer():
    return KimiDeltaAttention(HIDDEN, HEADS, DH, jnp.float32)


@pytest.fixture(scope="module")
def seeded():
    """(params, inputs (3, 40, hidden), positions, the no-cache form's
    output): decays of ~0.95 to ~0.9997, the rates the module sets."""
    layer = _layer()
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 40, HIDDEN))
    pos = jnp.broadcast_to(jnp.arange(40), (3, 40))
    params = layer.init(jax.random.PRNGKey(1), x, pos)["params"]
    np.testing.assert_allclose(
        np.exp(params["A_log"]), np.geomspace(*DECAY_RATES, HEADS), rtol=1e-5)
    assert not np.asarray(params["dt_bias"]).any()
    # a learned norm vector and a bias that are not their initial values
    params = {**params, "o_norm": 1.0 + 0.3 * jnp.cos(jnp.arange(DH)),
              "dt_bias": 0.2 * jnp.sin(jnp.arange(HEADS * DH))}
    return params, x, pos, np.asarray(layer.apply({"params": params}, x, pos))


def _zero_cache(layer, b):
    shapes = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), jnp.zeros((b, 4, HIDDEN)),
        jnp.zeros((b, 4), jnp.int32), decode=True))
    assert shapes["counters"]["kda"].shape == (len(COUNTS.entries),)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        shapes["cache"])


@pytest.mark.parametrize("cuts", [(7, 20, 33), (1, 2, 39), (16, 32)],
                         ids=["3_chunks", "single_tokens_first", "even"])
def test_chunks_then_steps_are_the_whole_sequence(seeded, cuts):
    """A chunk cut at any boundary, then single-token steps under
    per-row cursors: the outputs of the no-cache form, and one state."""
    params, x, pos, whole = seeded
    layer = _layer()
    cache = _zero_cache(layer, 3)
    assert {k: v.shape for k, v in cache.items()} == {
        "state": (3, HEADS, DH, DH), "conv": (3, 3, 3 * HEADS * DH),
        "cache_index": ()}
    outs, lo = [], 0
    for hi in cuts:
        y, upd = layer.apply(
            {"params": params, "cache": cache}, x[:, lo:hi], pos[:, lo:hi],
            decode=True, mutable=["cache", "counters"])
        cache, lo = upd["cache"], hi
        outs.append(y)
        np.testing.assert_allclose(
            upd["counters"]["kda"], [0, 0, 3 * y.shape[1], 1])
    for t in range(lo, 40):
        y, upd = layer.apply(
            {"params": params, "cache": cache}, x[:, t:t + 1],
            pos[:, t:t + 1], decode=True, mutable=["cache", "counters"],
            cache_cursor=jnp.full((3,), t))
        cache = upd["cache"]
        outs.append(y)
        np.testing.assert_allclose(
            upd["counters"]["kda"],
            [3, 3 * state_bytes_moved(1, HEADS, DH, DH), 0, 1])
    np.testing.assert_allclose(jnp.concatenate(outs, 1), whole, atol=2e-5)
    # the state after 40 tokens is the state one chunk of 40 leaves
    _, upd = layer.apply(
        {"params": params, "cache": _zero_cache(layer, 3)}, x, pos,
        decode=True, mutable=["cache", "counters"])
    np.testing.assert_allclose(cache["state"], upd["cache"]["state"],
                               atol=2e-5)
    np.testing.assert_allclose(cache["conv"], upd["cache"]["conv"], atol=1e-5)


def test_left_pads_write_nothing_decay_nothing_and_stay_out_of_the_conv(
        seeded):
    """Row r is left-padded by 5 r slots: its real tokens' outputs and
    its state are those of the unpadded sequence, in two chunks whose
    boundary falls inside one row's pads and another row's tokens."""
    params, x, pos, whole = seeded
    layer = _layer()
    pads = np.array([0, 5, 10])
    width = 40 + 10
    padded = np.zeros((3, width, HIDDEN), np.float32)
    # garbage in the pad slots: masked, it must not matter
    padded[:] = np.asarray(jax.random.normal(jax.random.PRNGKey(9),
                                             padded.shape))
    for r, p in enumerate(pads):
        padded[r, p:p + 40] = x[r]
    kv_mask = jnp.asarray(np.arange(width + 3)[None] >= pads[:, None])
    cache = _zero_cache(layer, 3)
    outs = []
    for lo, hi in ((0, 8), (8, width)):
        y, upd = layer.apply(
            {"params": params, "cache": cache}, jnp.asarray(padded[:, lo:hi]),
            None, decode=True, kv_mask=kv_mask, mutable=["cache", "counters"])
        cache = upd["cache"]
        outs.append(y)
    got = np.asarray(jnp.concatenate(outs, 1))
    for r, p in enumerate(pads):
        np.testing.assert_allclose(
            got[r, p:p + 40] - padded[r, p:p + 40],
            whole[r] - np.asarray(x[r]), atol=2e-5)
    # chunk_tokens counted the valid tokens alone
    assert float(upd["counters"]["kda"][2]) == sum(
        width - 8 - max(p - 8, 0) for p in pads)
    # the no-cache form reads kv_mask the same way
    fresh = np.asarray(layer.apply(
        {"params": params}, jnp.asarray(padded), None, kv_mask=kv_mask))
    np.testing.assert_allclose(fresh, got, atol=2e-5)


def test_a_row_without_a_request_keeps_its_state_under_the_cursor_form(
        seeded):
    params, x, pos, _ = seeded
    layer = _layer()
    cache = {**_zero_cache(layer, 3),
             "state": jax.random.normal(jax.random.PRNGKey(3),
                                        (3, HEADS, DH, DH))}
    kv_mask = jnp.asarray([[True] * 8, [False] * 8, [True] * 8])
    _, upd = layer.apply(
        {"params": params, "cache": cache}, x[:, :1], pos[:, :1], decode=True,
        kv_mask=kv_mask, cache_cursor=jnp.zeros((3,), jnp.int32),
        mutable=["cache", "counters"])
    np.testing.assert_array_equal(upd["cache"]["state"][1], cache["state"][1])
    assert np.abs(upd["cache"]["state"][0] - cache["state"][0]).max() > 1e-3
    assert float(upd["counters"]["kda"][0]) == 2
    with pytest.raises(ValueError, match="single-token"):
        layer.apply(
            {"params": params, "cache": cache}, x[:, :2], pos[:, :2],
            decode=True, cache_cursor=jnp.zeros((3,), jnp.int32),
            mutable=["cache", "counters"])
