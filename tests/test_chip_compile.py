"""Ahead-of-time compiles of the main path's Pallas kernels for a
described TPU v5e, at the 1.2B shapes ``chip_smoke.py`` runs them at.

The TPU's compiler is installed here and compiles for a chip that is
described and not attached (``jax.experimental.topologies``), so what
it refuses — a slice not aligned to the tiling, a block shape the
lowering rejects, too much VMEM — fails HERE, at no chip time.
Interpret-mode tests cannot see any of that: the paged kernels and the
page gather passed every one of them and were refused by Mosaic.

Rules this file keeps (on-chip-measurement guide, section 2):

- the topology, shardings and shapes are built inside a module-scoped,
  non-autouse fixture — never at import, in a ``skipif`` or in
  ``parametrize`` arguments — so every xdist worker collects the same
  tests and only the worker that RUNS this file loads the TPU library;
- the compiles run in the test's own process, in this one file;
- the persistent compilation cache is off around them (a compile for a
  described device is written to it but cannot be read back).

Nothing runs: these say a kernel builds, not that it is right or fast.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest

# 1.2B serving/train geometry (configs/lm_1p2b.yml, chip_smoke.py)
B, H, DH, L, T = 8, 16, 128, 2304, 128
S_TRAIN, B_TRAIN = 4096, 2
HIDDEN, MLP, VOCAB = 2048, 8192, 32768


@pytest.fixture(scope="module")
def chip():
    """``shape(dims, dtype)`` -> ShapeDtypeStruct on one described v5e
    chip; skips when the topology cannot be described here."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one_chip = SingleDeviceSharding(topo.devices[0])
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield lambda dims, dtype: jax.ShapeDtypeStruct(
            dims, dtype, sharding=one_chip
        )
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


def _compiles_to_a_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_attention_causal_s4096(chip, grad):
    from mlcomp_tpu.ops.pallas.flash_attention import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    qkv = [chip((B_TRAIN, S_TRAIN, H, DH), jnp.bfloat16)] * 3
    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    text = _compiles_to_a_kernel(fn, *qkv)
    # the kernel carries its trace name (GET /profile matches on it)
    assert "flash_fwd_kernel" in text
    if grad:
        # one backward kernel: this shape's float32 dq (4 MiB a KV head)
        # stays in VMEM beside dk and dv, inside the DEFAULT scoped limit
        # (the call carries no vmem_limit_bytes: DQ_RESIDENT_BUDGET)
        assert "flash_dq_dkv_kernel_tri" in text
        assert "flash_dkv_kernel" not in text
        # ... or XLA writes the default 16 MiB scoped limit on every op
        # of the program, and a train step's matmul fusions slow down
        assert '"offset":"0","size":"16777216"' not in text


def _dense_cache(chip, b=B, h_kv=H, l_buf=L):
    kv = chip((b, h_kv, l_buf, DH), jnp.int8)
    scale = chip((b, h_kv, 1, l_buf), jnp.bfloat16)
    bounds = chip((b,), jnp.int32)
    return kv, scale, kv, scale, bounds, bounds


# (slots, KV heads, buffer): chip_smoke.py's 1.2B daemon, and the
# benchmark's serve cells (InternLM2-1.8B, GQA 16/8, 48 slots of
# 2048 + 256 + 1 tokens rounded to 2560)
@pytest.mark.parametrize("b,h_kv,l_buf", [(B, H, L), (48, 8, 2560)],
                         ids=["smoke_1p2b", "cell_internlm2_1p8b"])
def test_decode_attention_dense_int8_kv(chip, b, h_kv, l_buf):
    from mlcomp_tpu.ops.pallas.decode_attention import decode_attention

    text = _compiles_to_a_kernel(
        functools.partial(decode_attention, interpret=False),
        chip((b, H, DH), jnp.bfloat16), *_dense_cache(chip, b, h_kv, l_buf),
    )
    # the benchmark's roofline reader matches the op by this name
    assert "decode_attention" in text


# + Laguna-S-2.1's serve cell (72 query heads a full layer over 8 KV
# heads, 48 slots of 1152)
# + SmallThinker-21BA3B's (28 query heads over 4 KV heads, groups of 7,
# 32 slots of 12,288 + 512 + 1 tokens rounded to 13,056: the walk's
# ring falls back to two slots there)
# + LFM2-24B-A2B's (224 slots of 3,840, the fattest slot of a ring of
# four: 8 x 768 tokens)
@pytest.mark.parametrize("b,h,h_kv,l_buf", [
    (B, H, H, L), (48, H, 8, 2560), (48, 72, 8, 1152), (32, 28, 4, 13056),
    (224, 32, 8, 3840),
], ids=["smoke_1p2b", "cell_internlm2_1p8b", "cell_laguna_full_layer",
        "cell_smallthinker", "cell_lfm2"])
def test_decode_attention_appends_in_place(chip, b, h, h_kv, l_buf):
    """The append form inside a K-step scan whose carry holds the
    caches, as the engine's dispatch program holds them: Mosaic takes
    the kernel (a select over one int8 tile, copies VMEM -> HBM at a
    dynamic tile offset), and XLA aliases all four caches through the
    call: no copy, slice or update-slice of a cache buffer is left."""
    import re

    from mlcomp_tpu.ops.pallas.decode_attention import decode_attention

    def steps(q, k8, ks, v8, vs, start, cur, kq, ks_new, vq, vs_new):
        def step(carry, _):
            q, caches, cur = carry
            out, *caches = decode_attention(
                q, *caches, kv_start=start, kv_stop=cur + 1,
                append=(kq, ks_new, vq, vs_new), interpret=False,
            )
            return ((out + q).astype(q.dtype), tuple(caches), cur + 1), None

        (q, caches, _), _ = jax.lax.scan(
            step, (q, (k8, ks, v8, vs), cur), None, length=4
        )
        return q, caches

    new_kv, new_scale = chip((b, h_kv, DH), jnp.int8), chip((b, h_kv), jnp.float32)
    text = jax.jit(steps, donate_argnums=(1, 2, 3, 4)).lower(
        chip((b, h, DH), jnp.bfloat16), *_dense_cache(chip, b, h_kv, l_buf),
        new_kv, new_scale, new_kv, new_scale,
    ).compile().as_text()
    assert "tpu_custom_call" in text and "decode_attention" in text
    cache_shaped = re.compile(
        rf"= (s8\[{b},{h_kv},{l_buf},{DH}\]|bf16\[{b},{h_kv},(1,)?{l_buf}\])"
        r"\S* (copy|copy-start|dynamic-update-slice|dynamic-slice)\("
    )
    assert not [ln for ln in text.splitlines() if cache_shaped.search(ln)]


def test_decode_attention_chunk_sq5(chip):
    from mlcomp_tpu.ops.pallas.decode_attention import decode_attention_chunk

    _compiles_to_a_kernel(
        functools.partial(decode_attention_chunk, interpret=False),
        chip((B, 5, H, DH), jnp.bfloat16), *_dense_cache(chip),
    )


@pytest.mark.parametrize("d,n,norm", [
    (HIDDEN, MLP, False),      # mlp up/gate
    (MLP, HIDDEN, False),      # mlp down
    (HIDDEN, VOCAB, False),    # lm head
    (HIDDEN, HIDDEN, False),   # attention projections
    (HIDDEN, MLP, True),       # RMSNorm folded into the kernel prologue
    (HIDDEN, VOCAB, True),
], ids=["mlp_up", "mlp_down", "lm_head", "attn_proj", "mlp_up_norm",
        "lm_head_norm"])
def test_quant_matmul(chip, d, n, norm):
    from mlcomp_tpu.ops.pallas.quant_matmul import quant_matmul

    def fn(x, q8, scale, g):
        return quant_matmul(
            x, q8, scale, interpret=False, norm_scale=g if norm else None
        )

    _compiles_to_a_kernel(
        fn, chip((B, d), jnp.bfloat16), chip((d, n), jnp.int8),
        chip((n,), jnp.float32), chip((d,), jnp.float32),
    )


def test_quant_matmul_one_row_head_with_the_norm_folded(chip):
    """A prefill chunk's logits in the int8 configuration: the chunk's
    last row alone through the 92,544-wide head, the final norm in the
    kernel's prologue (``last_logits_only`` under ``fold_norms``)."""
    from mlcomp_tpu.ops.pallas.quant_matmul import quant_matmul

    _compiles_to_a_kernel(
        lambda x, q8, scale, g: quant_matmul(
            x, q8, scale, interpret=False, norm_scale=g),
        chip((1, HIDDEN), jnp.bfloat16), chip((HIDDEN, 92544), jnp.int8),
        chip((92544,), jnp.float32), chip((HIDDEN,), jnp.float32),
    )


def _paged_pool(chip):
    mp = L // T
    pages = B * mp + 2          # + the reserved NULL and GRAVE pages
    kv = chip((pages, H, T, DH), jnp.int8)
    scale = chip((pages, H, 1, T), jnp.bfloat16)
    table = chip((B, mp), jnp.int32)
    bounds = chip((B,), jnp.int32)
    return kv, scale, kv, scale, table, bounds, bounds


@pytest.mark.parametrize("fetch", ["double", "rolled"])
def test_paged_decode_attention_page128(chip, fetch):
    from mlcomp_tpu.ops.pallas.decode_attention import paged_decode_attention

    _compiles_to_a_kernel(
        functools.partial(
            paged_decode_attention, interpret=False, fetch=fetch
        ),
        chip((B, H, DH), jnp.bfloat16), *_paged_pool(chip),
    )


@pytest.mark.parametrize("leaf", ["int8_kv", "bf16_kv", "bf16_scale"])
def test_page_gather_page128(chip, leaf):
    from mlcomp_tpu.kvpool.layout import _gather_leaf_pallas

    kv, scale, _, _, table, _, _ = _paged_pool(chip)
    pages = {
        "int8_kv": kv,
        "bf16_kv": chip(kv.shape, jnp.bfloat16),
        "bf16_scale": scale,
    }[leaf]
    _compiles_to_a_kernel(_gather_leaf_pallas, pages, table)


# the mixed-layer cell (rollout-offline): 48 slots of 256 + 768 + 1
# tokens, GQA groups of 9 (72 heads) and 6 (48) over 8 KV heads, a
# 512-token window; 128 experts held, hidden 3072, expert width 1024
MIXED_B, MIXED_L, MIXED_HKV = 48, 1152, 8


@pytest.mark.parametrize("heads", [72, 48], ids=["sliding_72", "full_48"])
def test_decode_attention_mixed_layer_groups(chip, heads):
    from mlcomp_tpu.ops.pallas.decode_attention import decode_attention

    _compiles_to_a_kernel(
        functools.partial(decode_attention, interpret=False),
        chip((MIXED_B, heads, DH), jnp.bfloat16),
        *_dense_cache(chip, MIXED_B, MIXED_HKV, MIXED_L),
    )


def test_decode_attention_chunk_window_256_queries(chip):
    """A 256-token chunk against the cache in a window layer: eight
    query tiles of 32 x 9 sublane rows, a start per query row."""
    from mlcomp_tpu.ops.pallas.decode_attention import decode_attention_chunk

    _compiles_to_a_kernel(
        functools.partial(decode_attention_chunk, interpret=False,
                          window=512),
        chip((1, 256, 72, DH), jnp.bfloat16),
        *_dense_cache(chip, 1, MIXED_HKV, MIXED_L),
    )


def test_decode_attention_chunk_window_2048_queries_12k_cache(chip):
    """SmallThinker's admission chunk: 2,048 queries of 28 heads (7 a
    KV head) against one slot's 13,056-token cache, window 4,096."""
    from mlcomp_tpu.ops.pallas.decode_attention import decode_attention_chunk

    _compiles_to_a_kernel(
        functools.partial(decode_attention_chunk, interpret=False,
                          window=4096),
        chip((1, 2048, 28, DH), jnp.bfloat16),
        *_dense_cache(chip, 1, 4, 13056),
    )


# Laguna-S-2.1's share (128 experts held of 256, top 10, SwiGLU),
# SmallThinker's whole layer (64 experts, top 6, ReGLU) and
# Kimi-Linear's share (32 held of 256, top 8), a decode step and an
# admission chunk each, in the row tile the layer gives the call
# (auto_row_tile: 16 but for the two 2,048-token chunks' 128 and 64);
# and the largest tile at the widest blocks served (Laguna's widths, a
# 4,096-token chunk), which is what the rule's cap has to fit in VMEM
@pytest.mark.parametrize("tokens,published,e,h,f,k,gate,tile", [
    (48, 256, 128, 3072, 1024, 10, "silu", 16),
    (256, 256, 128, 3072, 1024, 10, "silu", 16),
    (32, 64, 64, 2560, 768, 6, "relu", 16),
    (2048, 64, 64, 2560, 768, 6, "relu", 128),
    (112, 256, 32, 2304, 1024, 8, "silu", 16),
    (2048, 256, 32, 2304, 1024, 8, "silu", 64),
    (4096, 256, 128, 3072, 1024, 10, "silu", 128),
], ids=["decode", "chunk", "smallthinker_decode", "smallthinker_chunk",
        "kimi_decode", "kimi_chunk", "largest_tile_widest_blocks"])
def test_grouped_matmul_held_experts(chip, tokens, published, e, h, f, k,
                                     gate, tile):
    from mlcomp_tpu.ops.pallas.grouped_matmul import (
        ROW_TILES,
        auto_row_tile,
        grouped_matmul,
        padded_rows,
    )

    tm = auto_row_tile(tokens, k, published)
    assert tm == tile <= ROW_TILES[-1]
    rows = padded_rows(tokens * k, e, tm)
    tiles = (chip((rows // tm,), jnp.int32), chip((1,), jnp.int32))

    def experts(x, w_gate, w_up, w_down, tile_group, used):
        act = grouped_matmul(x, w_gate, tile_group, used, w2=w_up,
                             interpret=False, gate=gate)
        return grouped_matmul(act, w_down, tile_group, used, interpret=False)

    text = _compiles_to_a_kernel(
        experts, chip((rows, h), jnp.bfloat16),
        chip((e, h, f), jnp.bfloat16), chip((e, h, f), jnp.bfloat16),
        chip((e, f, h), jnp.bfloat16), *tiles,
    )
    # the benchmark's roofline reader matches the op by this name
    assert "grouped_matmul" in text


# Brumby-14B's cell (continuation-offline): 20 slots, 5 query heads a
# KV head over 8 KV heads of 128, a float32 state of 8,320 x 128 a head
@pytest.mark.parametrize("product", ["bfloat16", "float32"])
def test_retention_step_walks_the_state_in_place(chip, product):
    from mlcomp_tpu.ops.pallas.retention import (
        expanded_width,
        retention_step,
    )

    b, n, g = 20, 8, 5
    width = expanded_width(DH)

    def step(q, k, v, log_g, live, state, norm):
        return retention_step(q, k, v, log_g, live, state, norm, eps=1e-6,
                              product_dtype=product, interpret=False)

    args = (chip((b, n, g, DH), jnp.bfloat16), chip((b, n, DH), jnp.bfloat16),
            chip((b, n, DH), jnp.bfloat16), chip((b, n), jnp.float32),
            chip((b,), jnp.bool_), chip((b, n, width, DH), jnp.float32),
            chip((b, n, width), jnp.float32))
    compiled = jax.jit(step, donate_argnums=(5, 6)).lower(*args).compile()
    text = compiled.as_text()
    # the benchmark's readers match the op by this name
    assert "tpu_custom_call" in text and "retention_step" in text
    # the state and the normaliser are updated where they are
    state_bytes = b * n * width * (DH + 1) * 4
    assert compiled.memory_analysis().alias_size_in_bytes >= state_bytes


# Kimi-Linear's cell (reasoning-offline): 112 slots, 32 KDA heads with a
# float32 state of 128 x 128 each; a latent buffer of 9,729 slots kept
# as 10,240 x 640 bfloat16, 32 heads' absorbed queries against it
def test_kda_step_updates_the_states_in_place(chip):
    from mlcomp_tpu.ops.pallas.kda import kda_step

    b, n = 112, 32

    def step(q, k, v, log_a, beta, live, state):
        return kda_step(q, k, v, log_a, beta, live, state, interpret=False)

    args = (chip((b, n, DH), jnp.float32), chip((b, n, DH), jnp.float32),
            chip((b, n, DH), jnp.float32), chip((b, n, DH), jnp.float32),
            chip((b, n), jnp.float32), chip((b,), jnp.bool_),
            chip((b, n, DH, DH), jnp.float32))
    compiled = jax.jit(step, donate_argnums=(6,)).lower(*args).compile()
    text = compiled.as_text()
    # the benchmark's readers match the op by this name
    assert "tpu_custom_call" in text and "kda_step" in text
    # the states are updated where they are
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= b * n * DH * DH * 4


def test_latent_decode_appends_in_place_and_reads_a_block_once(chip):
    from mlcomp_tpu.ops.pallas.latent_attention import (
        buffer_len,
        latent_decode,
    )

    b, n, dc, width = 112, 32, 512, 640
    length = buffer_len(8192 + 1536 + 1)
    assert length == 10240

    def step(q, new, cache, start, stop):
        return latent_decode(q, new, cache, start, stop, dc=dc,
                             interpret=False)

    args = (chip((b, n, width), jnp.bfloat16), chip((b, width), jnp.bfloat16),
            chip((b, length, width), jnp.bfloat16), chip((b,), jnp.int32),
            chip((b,), jnp.int32))
    compiled = jax.jit(step, donate_argnums=(2,)).lower(*args).compile()
    text = compiled.as_text()
    # the benchmark's readers match the op by this name
    assert "tpu_custom_call" in text and "latent_decode" in text
    # the cache is written where it is: no second buffer of 1.47 GB
    cache_bytes = b * length * width * 2
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= cache_bytes
    assert stats.temp_size_in_bytes < cache_bytes // 100


# LFM2-24B-A2B's cell (synthesis-offline): 224 slots, 32 query heads
# over 8 KV heads of 64 stored in 128 lanes (the int8 cache pads a head
# to a lane tile: the kernel sees a head of 128), a buffer of 2,048 +
# 1,536 + 1 tokens rounded to 3,840; its chunk form at 2,048 queries;
# and the experts at a step's 896 and a chunk's 8,192 assignments over
# 64 experts of 2,048 x 1,536
LFM2_B, LFM2_H, LFM2_HKV, LFM2_L = 224, 32, 8, 3840


def test_decode_attention_224_rows_of_64_wide_heads_in_128_lanes(chip):
    from mlcomp_tpu.ops.pallas.decode_attention import (
        decode_attention,
        pick_buffer_len,
    )

    assert pick_buffer_len(2048 + 1536 + 1, LFM2_HKV, DH) == LFM2_L
    text = _compiles_to_a_kernel(
        functools.partial(decode_attention, interpret=False),
        chip((LFM2_B, LFM2_H, DH), jnp.bfloat16),
        *_dense_cache(chip, LFM2_B, LFM2_HKV, LFM2_L),
    )
    # gqa64_decode_attn_roofline matches the op by this name
    assert "decode_attention" in text


def test_decode_attention_chunk_2048_queries_of_4_heads_a_kv_head(chip):
    from mlcomp_tpu.ops.pallas.decode_attention import decode_attention_chunk

    _compiles_to_a_kernel(
        functools.partial(decode_attention_chunk, interpret=False),
        chip((1, 2048, LFM2_H, DH), jnp.bfloat16),
        *_dense_cache(chip, 1, LFM2_HKV, LFM2_L),
    )


@pytest.mark.parametrize("tokens,tile", [(224, 16), (2048, 128)],
                         ids=["lfm2_decode", "lfm2_chunk"])
def test_grouped_matmul_64_experts_of_2048_by_1536(chip, tokens, tile):
    from mlcomp_tpu.ops.pallas.grouped_matmul import (
        auto_row_tile,
        grouped_matmul,
        padded_rows,
    )

    e, h, f, k = 64, 2048, 1536, 4
    assert tokens * k in (896, 8192)
    tm = auto_row_tile(tokens, k, e)
    assert tm == tile
    rows = padded_rows(tokens * k, e, tm)
    tiles = (chip((rows // tm,), jnp.int32), chip((1,), jnp.int32))

    def experts(x, w_gate, w_up, w_down, tile_group, used):
        act = grouped_matmul(x, w_gate, tile_group, used, w2=w_up,
                             interpret=False, gate="silu")
        return grouped_matmul(act, w_down, tile_group, used, interpret=False)

    text = _compiles_to_a_kernel(
        experts, chip((rows, h), jnp.bfloat16),
        chip((e, h, f), jnp.bfloat16), chip((e, h, f), jnp.bfloat16),
        chip((e, f, h), jnp.bfloat16), *tiles,
    )
    assert "grouped_matmul" in text


# LongCat-Flash-Chat's cell (document-qa-offline): 24 slots, 64 heads
# over a latent of 512 + 64 in 640 lanes, a buffer of 8,192 + 256 + 1
# tokens rounded to 8,704; 16 experts of 6,144 x 2,048 held of a router
# 768 wide, top 12: a step's 288 assignments and a chunk's 24,576
LONGCAT_B, LONGCAT_H, LONGCAT_HIDDEN, LONGCAT_F = 24, 64, 6144, 2048


def test_latent_decode_at_64_heads_a_row(chip):
    """``ROWS_PER_STEP`` x 64 x 640 query blocks and a 64 x 512 float32
    accumulator a row: the constants Kimi-Linear's 32 heads set."""
    from mlcomp_tpu.ops.pallas.latent_attention import (
        buffer_len,
        latent_decode,
    )

    b, n, dc, width = LONGCAT_B, LONGCAT_H, 512, 640
    length = buffer_len(8192 + 256 + 1)
    assert length == 8704

    def step(q, new, cache, start, stop):
        return latent_decode(q, new, cache, start, stop, dc=dc,
                             interpret=False)

    args = (chip((b, n, width), jnp.bfloat16), chip((b, width), jnp.bfloat16),
            chip((b, length, width), jnp.bfloat16), chip((b,), jnp.int32),
            chip((b,), jnp.int32))
    compiled = jax.jit(step, donate_argnums=(2,)).lower(*args).compile()
    text = compiled.as_text()
    # mla_decode_roofline matches the op by this name
    assert "tpu_custom_call" in text and "latent_decode" in text
    cache_bytes = b * length * width * 2
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= cache_bytes
    assert stats.temp_size_in_bytes < cache_bytes // 100


@pytest.mark.parametrize("tokens,tile", [(LONGCAT_B, 16), (2048, 32)],
                         ids=["longcat_decode", "longcat_chunk"])
def test_grouped_matmul_16_experts_of_6144_by_2048(chip, tokens, tile):
    """The widest contraction served: (6144, 256) weight blocks in the
    front half, (2048, 768) in the back, beside the call's row tile."""
    from mlcomp_tpu.ops.pallas.grouped_matmul import (
        auto_block_n,
        auto_row_tile,
        grouped_matmul,
        padded_rows,
    )

    e, h, f, k, routed = 16, LONGCAT_HIDDEN, LONGCAT_F, 12, 512 + 256
    tm = auto_row_tile(tokens, k, routed)
    assert tm == tile
    assert (auto_block_n(h, f, 2), auto_block_n(f, h, 2)) == (256, 768)
    rows = padded_rows(tokens * k, e, tm)
    tiles = (chip((rows // tm,), jnp.int32), chip((1,), jnp.int32))

    def experts(x, w_gate, w_up, w_down, tile_group, used):
        act = grouped_matmul(x, w_gate, tile_group, used, w2=w_up,
                             interpret=False, gate="silu")
        return grouped_matmul(act, w_down, tile_group, used, interpret=False)

    text = _compiles_to_a_kernel(
        experts, chip((rows, h), jnp.bfloat16),
        chip((e, h, f), jnp.bfloat16), chip((e, h, f), jnp.bfloat16),
        chip((e, f, h), jnp.bfloat16), *tiles,
    )
    assert "grouped_matmul" in text


@pytest.mark.parametrize("tokens,tile", [(LONGCAT_B, 16), (2048, 32)],
                         ids=["longcat_decode", "longcat_chunk"])
def test_the_held_rows_gather_and_pick_at_6144_wide(chip, tokens, tile):
    """XLA's own gathers around the expert kernels: the sorted buffer's
    rows from the tokens, and each assignment's row back from the
    buffer, bf16[rows, 6144].  The same fusion ran out of scoped VMEM at
    bf16[1024, 2304] (Kimi-Linear at 128 slots): a refusal shows here."""
    from mlcomp_tpu.ops.pallas.grouped_matmul import padded_rows

    k, h = 12, LONGCAT_HIDDEN
    rows = padded_rows(tokens * k, 16, tile)

    def around(x, row_source, out, dest):
        held = dest < rows
        picked = jnp.where(
            held[:, None],
            jnp.take(out, jnp.where(held, dest, 0), axis=0), 0,
        ).reshape(tokens, k, h)
        return jnp.take(x, row_source, axis=0), picked

    text = jax.jit(around).lower(
        chip((tokens, h), jnp.bfloat16), chip((rows,), jnp.int32),
        chip((rows, h), jnp.bfloat16), chip((tokens * k,), jnp.int32),
    ).compile().as_text()
    assert "gather" in text
