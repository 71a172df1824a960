"""The grouped matmul (interpret mode) against plain ``jnp``: empty
experts, experts not held, one expert taking every row."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlcomp_tpu.ops.pallas.grouped_matmul import (
    ROW_TILE,
    ROW_TILES,
    auto_row_tile,
    group_layout,
    grouped_matmul,
    padded_rows,
)

G, K, N = 4, 128, 256


def _weights(seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    w = jax.random.normal(k1, (G, K, N), jnp.float32) * K ** -0.5
    w2 = jax.random.normal(k2, (G, K, N), jnp.float32) * K ** -0.5
    return w, w2


# a value outside [0, G) is an assignment whose expert is not held
CASES = {
    "mixed": [0, 3, 3, 7, 1, 0, -1, 3, 5, 0, 3, 3, 3, 1, 4, 0, 3, 3, 3, 3],
    "empty_experts": [2, 2, 2, 2, 2, 0],
    "none_held": [4, 5, 6, 7, 9],
    "one_takes_all": [1] * 37,
    "all_not_held_but_one": [9, 9, 9, 2, 9],
    # more rows than any tile on one expert, beside empty ones
    "300_on_one": [2] * 300,
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("swiglu", [False, True])
@pytest.mark.parametrize("tm", [16, 64, 128])
def test_grouped_matmul_matches_jnp(case, swiglu, tm):
    group = jnp.asarray(CASES[case], jnp.int32)
    a = group.shape[0]
    x = jax.random.normal(jax.random.PRNGKey(a), (a, K), jnp.float32)
    w, w2 = _weights(3)
    lay = group_layout(group, G, tm)
    rows = padded_rows(a, G, tm)
    assert lay.row_source.shape == (rows,)
    held = np.asarray((group >= 0) & (group < G))
    assert np.array_equal(np.asarray(lay.dest) < rows, held)
    sizes = np.bincount(np.asarray(group)[held], minlength=G)
    assert np.array_equal(np.asarray(lay.sizes), sizes)
    assert int(lay.tiles_used[0]) == sum(-(-s // tm) for s in sizes)
    out = grouped_matmul(
        jnp.take(x, lay.row_source, axis=0), w, lay.tile_group,
        lay.tiles_used, w2=w2 if swiglu else None, interpret=True,
    )
    dest = np.asarray(lay.dest)
    # every held assignment's row sits in a tile of its own expert
    tile_group = np.asarray(lay.tile_group)
    for i in np.flatnonzero(held):
        assert tile_group[dest[i] // tm] == int(group[i])
        want = x[i] @ w[int(group[i])]
        if swiglu:
            want = jax.nn.silu(want) * (x[i] @ w2[int(group[i])])
        np.testing.assert_allclose(
            np.asarray(out[dest[i]]), np.asarray(want), rtol=2e-5, atol=2e-5
        )
    # held assignments of one expert keep their arrival order
    for g in range(G):
        mine = dest[np.asarray(group) == g]
        assert np.all(np.diff(mine) == 1)


@pytest.mark.parametrize("case", ["mixed", "empty_experts", "one_takes_all"])
def test_the_relu_gate_matches_jnp(case):
    """ReGLU's front half, ``relu(x @ w[g]) * (x @ w2[g])``, through the
    one kernel; a gate it has no name for is refused."""
    group = jnp.asarray(CASES[case], jnp.int32)
    a = group.shape[0]
    x = jax.random.normal(jax.random.PRNGKey(a), (a, K), jnp.float32)
    w, w2 = _weights(5)
    lay = group_layout(group, G, ROW_TILE)
    rows = jnp.take(x, lay.row_source, axis=0)
    out = grouped_matmul(rows, w, lay.tile_group, lay.tiles_used, w2=w2,
                         interpret=True, gate="relu")
    silu = grouped_matmul(rows, w, lay.tile_group, lay.tiles_used, w2=w2,
                          interpret=True)
    dest = np.asarray(lay.dest)
    held = np.flatnonzero(np.asarray((group >= 0) & (group < G)))
    for i in held:
        g = int(group[i])
        want = jnp.maximum(x[i] @ w[g], 0.0) * (x[i] @ w2[g])
        np.testing.assert_allclose(
            np.asarray(out[dest[i]]), np.asarray(want), rtol=2e-5, atol=2e-5)
    # and it is not the SiLU gate under another name
    assert np.abs(np.asarray(out - silu)[dest[held]]).max() > 1e-2
    with pytest.raises(ValueError, match="gate 'gelu'"):
        grouped_matmul(rows, w, lay.tile_group, lay.tiles_used, w2=w2,
                       interpret=True, gate="gelu")


# tokens, experts a token, published experts -> the tile: a decode
# step's 2-4 rows an expert and a 256-token chunk's 10 keep the smallest
# (a larger one would be padding, and these calls compile the programs
# they compiled); the two 2,048-token chunks take what their experts fill
@pytest.mark.parametrize("shape,tm", [
    ((48, 10, 256), ROW_TILE), ((32, 6, 64), ROW_TILE),
    ((112, 8, 256), ROW_TILE), ((256, 10, 256), ROW_TILE),
    ((2048, 6, 64), 128), ((2048, 8, 256), 64),
], ids=["laguna_step", "smallthinker_step", "kimi_step", "laguna_chunk",
        "smallthinker_chunk", "kimi_chunk"])
def test_the_tile_follows_the_rows_an_expert_can_expect(shape, tm):
    assert auto_row_tile(*shape) == tm


def test_the_rule_gives_only_tiles_the_layout_and_the_kernel_take():
    """Over every shape up to a long chunk: one of the swept tiles, a
    whole number of bf16 sublane tiles, never more rows than an expert
    can expect (but for the floor), and growing with the call."""
    assert ROW_TILES[0] == ROW_TILE and all(
        tm % ROW_TILE == 0 for tm in ROW_TILES)
    for k, experts in ((1, 8), (2, 4), (6, 64), (8, 256), (10, 256)):
        last = ROW_TILE
        for tokens in (1, 7, 32, 100, 256, 1000, 2048, 4096, 65536):
            tm = auto_row_tile(tokens, k, experts)
            assert tm in ROW_TILES and tm >= last
            assert tm == ROW_TILE or tm <= tokens * k / experts
            lay = jax.eval_shape(
                lambda g, tm=tm: group_layout(g, 4, tm),
                jax.ShapeDtypeStruct((tokens * k,), jnp.int32))
            assert lay.row_source.shape[0] % tm == 0
            last = tm
