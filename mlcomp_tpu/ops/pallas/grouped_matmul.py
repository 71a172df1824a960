"""Grouped matmul: rows sorted by group, one weight matrix a group.

The expert layer's product (``models/moe.py`` ``RoutedExperts``): the
tokens routed to the experts this chip holds, sorted by expert, each
against its own expert's matrix.  An expert's weights are read only if
a token reached it, and then once per output block.

Layout (made by :func:`group_layout`, a counting sort, no comparison
sort): each group's rows sit together and the group is padded to a
whole number of ``tm``-row tiles, so a tile belongs to one group.
``tile_group`` names each tile's group and rides the scalar prefetch:
the weight block's index map reads it, so consecutive tiles of one
group keep their weight block in VMEM and a group with no row has no
tile.  The tiles past the last used one are named after the last used
group (their weight block is the one already there, no copy starts),
fetch the last used rows' block (the same) and compute nothing; the
rows they would write are never read back.

One kernel serves the plain product ``x @ w[g]`` and, with a second
stack ``w2``, a gated unit's front half ``act(x @ w[g]) * (x @ w2[g])``
(one read of the rows, one write of the product), ``act`` one of
:data:`GATES` (SiLU: SwiGLU; ReLU: ReGLU).  The contraction is
whole in one block (the model's hidden size in the front half, the
expert width in the back: 3072 / 1024, 2560 / 768, 2304 / 1024, 2048 /
1536 and 6144 / 2048 at the served configurations), so there is no
accumulator and no K
loop, and a row's product does not depend on its tile; the grid is
(output blocks, row tiles) with the row tiles innermost.

The row tile is the caller's (:func:`auto_row_tile`): a grid step
fetches the next step's blocks while it multiplies, one step ahead, so
a group's weight block (2 to 6 MB with both stacks) hides only behind a
step that multiplies long enough.  A decode step's few rows an expert
are bound by the weights' bytes whatever the tile, and a larger tile is
padding; a prefill chunk's hundreds of rows an expert in 16-row tiles
were hundreds of grid steps of 0.3 us of products each (3,312 a layer
at 2,048 tokens x 6 over 64 experts, at 36% of the call's roofline on
the chip).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mlcomp_tpu.ops.pallas import interpret_default

LANES = 128
# bf16 rows pack 16 to a sublane tile: the smallest row tile
ROW_TILE = 16
# the row tiles a layout is given (auto_row_tile).  On the chip (tools/
# exp_grouped_matmul.py, PR 42; ms a layer call at tiles of 16 / 32 / 64
# / 128 / 256, routing uniform, a Zipf-like draw within 3% of it):
# 2,048 tokens x 6 over 64 experts (192 rows an expert; 2560, 768) 3.88
# / 2.94 / 2.67 / 2.63 / 3.07; 2,048 x 8 over 256 of which 32 held (64
# rows; 2304, 1024) 2.23 / 1.90 / 1.69 / 1.59 / 1.58; 256 x 10 over 256
# of which 128 held (10 rows; 3072, 1024) 3.53 / 3.54 / 3.81 / 4.34 /
# refused; decode steps (2 to 3.5 rows) level from 16 to 32, then 2-5%
# slower a doubling.  No 256: its two kernel calls are the fastest at
# 192 rows an expert (1.57 ms against 2.01), but the padded buffers the
# layer gathers into and picks from grow with the tile (28,672 rows for
# 12,288 assignments) and cost more than that, and beside (3072, 512)
# weight blocks a 256-row tile does not fit the kernel's 16 MiB of VMEM
# (128 rows: 12 MiB of weight blocks, 1.5 of rows, 0.75 of products)
ROW_TILES = (16, 32, 64, 128)
# one weight block in VMEM (double-buffered, and twice over for SwiGLU's
# two stacks: 12 MiB of the 16 MiB a kernel may take by default).  On
# the chip, at Laguna's widths over 128 experts (tools/
# exp_grouped_matmul.py --blocks 256x1024,128x512,512x1536, PR 28):
# (3072, 512) / (1024, 1536) blocks took 2.84 ms a layer call at 48
# tokens where (3072, 256) / (1024, 1024) took 3.05 and (3072, 128) /
# (1024, 512) 3.08
WEIGHT_BLOCK_BYTES = 3 * 1024 * 1024
# the gate's activation in the fused front half, by name
GATES = {"silu": jax.nn.silu, "relu": jax.nn.relu}


class GroupLayout(NamedTuple):
    """Where each assignment's row sits in the padded, sorted buffer."""

    dest: jax.Array        # (A,) row of assignment a; ``rows`` if not held
    row_source: jax.Array  # (rows,) which assignment-source a row copies
    tile_group: jax.Array  # (rows // tm,) int32 group of each tile
    tiles_used: jax.Array  # (1,) int32 tiles that hold rows
    sizes: jax.Array       # (groups,) int32 rows of each group


def padded_rows(assignments: int, groups: int, tm: int) -> int:
    """Rows that hold any split of ``assignments`` rows over ``groups``
    groups, each padded to a multiple of ``tm``."""
    worst = assignments + min(groups, assignments) * (tm - 1)
    return -(-worst // tm) * tm


def group_layout(group: jax.Array, groups: int, tm: int,
                 source: Optional[jax.Array] = None) -> GroupLayout:
    """The sorted, tile-padded layout of ``group`` (A,) int32, where a
    value outside [0, groups) marks an assignment not held (it gets no
    row).  ``source`` (A,) is what a row copies from (default: the
    assignment's own index); pad rows copy source 0."""
    a = group.shape[0]
    rows = padded_rows(a, groups, tm)
    held = (group >= 0) & (group < groups)
    onehot = (
        group[:, None] == jnp.arange(groups, dtype=jnp.int32)[None]
    ).astype(jnp.int32)                                   # (A, G)
    # rank of an assignment among those of its group, in arrival order
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    sizes = jnp.sum(onehot, axis=0)
    padded = -(-sizes // tm) * tm
    ends = jnp.cumsum(padded)
    first = jnp.sum(onehot * (ends - padded)[None], axis=1)
    dest = jnp.where(held, first + rank, rows).astype(jnp.int32)
    if source is None:
        source = jnp.arange(a, dtype=jnp.int32)
    row_source = jnp.zeros((rows,), jnp.int32).at[dest].set(
        source.astype(jnp.int32), mode="drop"
    )
    tiles_used = (ends[-1] // tm).astype(jnp.int32)
    tile0 = jnp.arange(rows // tm, dtype=jnp.int32) * tm
    tile_group = jnp.searchsorted(ends, tile0, side="right").astype(jnp.int32)
    last = tile_group[jnp.maximum(tiles_used - 1, 0)]
    tile_group = jnp.where(
        tile0 < ends[-1], tile_group, jnp.minimum(last, groups - 1)
    )
    return GroupLayout(dest, row_source, tile_group,
                       tiles_used.reshape(1), sizes)


def _kernel(tg_ref, used_ref, x_ref, *refs, gate: Optional[str]):
    o_ref = refs[-1]

    @pl.when(pl.program_id(1) < used_ref[0])
    def _tile():
        x = x_ref[...]
        y = jnp.dot(x, refs[0][0], preferred_element_type=jnp.float32)
        if gate is not None:
            up = jnp.dot(x, refs[1][0], preferred_element_type=jnp.float32)
            y = GATES[gate](y) * up
        o_ref[...] = y.astype(o_ref.dtype)


def auto_block_n(k: int, n: int, itemsize: int) -> int:
    """Widest lane-multiple divisor of ``n`` whose (k, block) weight
    block fits :data:`WEIGHT_BLOCK_BYTES`."""
    fits = [
        b for b in range(LANES, n + 1, LANES)
        if n % b == 0 and k * b * itemsize <= WEIGHT_BLOCK_BYTES
    ]
    if not fits:
        raise NotImplementedError(
            f"no lane-multiple block of a ({k}, {n}) weight fits "
            f"{WEIGHT_BLOCK_BYTES} bytes"
        )
    return fits[-1]


def auto_row_tile(tokens: int, k: int, experts: int) -> int:
    """The row tile for a call that routes ``tokens`` tokens to ``k`` of
    ``experts`` experts each: the largest of :data:`ROW_TILES` that the
    rows an expert can expect, ``tokens * k / experts``, fill; never
    under :data:`ROW_TILE`.  ``experts`` is the PUBLISHED count, the
    router's whole width (zero-compute experts take their part of the
    choices too): a chip that holds a share of them sees that share of
    the assignments, so the rows a held expert gets do not depend on
    the share."""
    expected = tokens * k // experts
    return max([tm for tm in ROW_TILES if tm <= expected], default=ROW_TILE)


def grouped_matmul(
    x: jax.Array,
    w: jax.Array,
    tile_group: jax.Array,
    tiles_used: jax.Array,
    w2: Optional[jax.Array] = None,
    block_n: Optional[int] = None,
    interpret: Optional[bool] = None,
    gate: str = "silu",
) -> jax.Array:
    """``x`` (rows, K) in :func:`group_layout`'s order, ``w`` (G, K, N):
    row r of tile t times ``w[tile_group[t]]``; with ``w2`` the gated
    front half, ``gate`` naming the activation (:data:`GATES`).  Rows of
    tiles at or past ``tiles_used`` come back unwritten.  Returns
    (rows, N) in ``x.dtype``."""
    rows, k = x.shape
    g, k_w, n = w.shape
    n_tiles = tile_group.shape[0]
    if gate not in GATES:
        raise ValueError(f"gate {gate!r} not among {sorted(GATES)}")
    if k_w != k or rows % n_tiles or (w2 is not None and w2.shape != w.shape):
        raise ValueError(
            f"x {x.shape}, w {w.shape}, w2 "
            f"{None if w2 is None else w2.shape}, {n_tiles} tiles"
        )
    tm = rows // n_tiles
    if tm % ROW_TILE or k % LANES or n % LANES:
        raise NotImplementedError(
            f"row tile {tm} must be a multiple of {ROW_TILE}, K {k} and "
            f"N {n} of {LANES}"
        )
    if interpret is None:
        interpret = interpret_default()
    tn = block_n or auto_block_n(k, n, w.dtype.itemsize)
    if n % tn:
        raise ValueError(f"block_n {tn} does not divide N {n}")

    def rows_of(j, i, tg, used):
        # an unused tile names the last used one: no new copy
        return (jnp.minimum(i, jnp.maximum(used[0] - 1, 0)), 0)

    def weight_of(j, i, tg, used):
        return (tg[i], 0, j)

    weights = [w] if w2 is None else [w, w2]
    return pl.pallas_call(
        functools.partial(_kernel, gate=None if w2 is None else gate),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, n_tiles),
            in_specs=[pl.BlockSpec((tm, k), rows_of)] + [
                pl.BlockSpec((1, k, tn), weight_of) for _ in weights
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, i, *_: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
        name="grouped_matmul",
    )(tile_group.astype(jnp.int32), tiles_used.astype(jnp.int32), x, *weights)
