"""Flash attention kernel vs the XLA reference path (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import pallas_calls

from mlcomp_tpu.ops.attention import reference_attention
from mlcomp_tpu.ops.pallas.flash_attention import flash_attention


def _rand(shape, seed, dtype=jnp.float32):
    return jnp.asarray(np.random.RandomState(seed).normal(size=shape), dtype)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(causal):
    q = _rand((2, 256, 2, 64), 0)
    k = _rand((2, 256, 2, 64), 1)
    v = _rand((2, 256, 2, 64), 2)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_kv=128)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_forward_gqa_and_cross_lengths():
    # 4 query heads sharing 2 kv heads; Sq != Sk
    q = _rand((1, 256, 4, 64), 0)
    k = _rand((1, 384, 2, 64), 1)
    v = _rand((1, 384, 2, 64), 2)
    out = flash_attention(q, k, v, block_q=128, block_kv=128)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_reference(causal):
    q = _rand((1, 128, 2, 64), 3)
    k = _rand((1, 128, 2, 64), 4)
    v = _rand((1, 128, 2, 64), 5)
    w = _rand((1, 128, 2, 64), 6)  # fixed cotangent-shaping weights

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=128, block_kv=128) * w)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) * w)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_grads_gqa():
    q = _rand((1, 128, 4, 64), 7)
    k = _rand((1, 128, 2, 64), 8)
    v = _rand((1, 128, 2, 64), 9)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       block_q=128, block_kv=128) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_small_sequences_fall_back():
    q = _rand((1, 64, 2, 64), 0)
    with pytest.raises(NotImplementedError):
        flash_attention(q, q, q)


@pytest.mark.parametrize("causal", [False, True])
def test_ragged_length_stays_on_kernel(causal):
    """S % 128 != 0 pads to a block multiple instead of falling back."""
    s = 777
    q = _rand((1, s, 2, 64), 20)
    k = _rand((1, s, 2, 64), 21)
    v = _rand((1, s, 2, 64), 22)
    out = flash_attention(q, k, v, causal=causal)
    assert out.shape == q.shape
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ragged_length_grads(causal):
    s = 333
    q = _rand((1, s, 2, 64), 23)
    k = _rand((1, s, 2, 64), 24)
    v = _rand((1, s, 2, 64), 25)
    w = _rand((1, s, 2, 64), 26)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) * w)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) * w)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_ragged_length_with_kv_stop():
    """Ragged S composes with caller-provided key windows."""
    b, s_q, s_k = 2, 200, 300
    q = _rand((b, s_q, 2, 64), 27)
    k = _rand((b, s_k, 2, 64), 28)
    v = _rand((b, s_k, 2, 64), 29)
    stop = jnp.asarray([300, 170], jnp.int32)
    out = flash_attention(q, k, v, kv_stop=stop)
    ref = reference_attention(
        q, k, v, mask=_window_mask(b, s_k, np.zeros(b, np.int64), np.asarray(stop))
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_causal_block_skip_numerics():
    """Multi-block causal (exercises the dead-block index clamping in all
    three kernels) still matches the reference bit-for-bit-ish."""
    s = 384
    q = _rand((1, s, 2, 64), 30)
    k = _rand((1, s, 2, 64), 31)
    v = _rand((1, s, 2, 64), 32)
    w = _rand((1, s, 2, 64), 33)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, block_q=128, block_kv=128) * w
        )

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) * w)

    np.testing.assert_allclose(
        float(loss_flash(q, k, v)), float(loss_ref(q, k, v)), rtol=1e-5
    )
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_dispatch_env_off(monkeypatch):
    from mlcomp_tpu.ops.attention import dot_product_attention

    monkeypatch.setenv("MLCOMP_TPU_FLASH", "off")
    q = _rand((1, 128, 2, 64), 0)
    out = dot_product_attention(q, q, q, causal=True)
    ref = reference_attention(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def _window_mask(b, s_k, lo, hi):
    cols = np.arange(s_k)[None]
    return jnp.asarray(
        ((cols >= np.asarray(lo)[:, None]) & (cols < np.asarray(hi)[:, None]))
    )[:, None, None, :]


@pytest.mark.parametrize("causal", [False, True])
def test_kv_bounds_match_masked_reference(causal):
    """Per-row [start, stop) key windows == the equivalent dense mask."""
    b, s = 3, 256
    q = _rand((b, s, 4, 64), 10)
    k = _rand((b, s, 2, 64), 11)
    v = _rand((b, s, 2, 64), 12)
    lo = np.asarray([0, 17, 128])
    hi = np.asarray([256, 256, 200])
    out = flash_attention(
        q, k, v, causal=causal,
        kv_start=jnp.asarray(lo), kv_stop=jnp.asarray(hi),
        block_q=128, block_kv=128,
    )
    ref = reference_attention(
        q, k, v, causal=causal, mask=_window_mask(b, s, lo, hi)
    )
    out_np, ref_np = np.asarray(out), np.asarray(ref)
    if causal:
        # rows whose causal∩window key set is empty: kernel outputs 0 by
        # contract, the XLA path degrades to a uniform average — compare
        # only rows with at least one valid key
        rows = np.arange(s)[None] >= lo[:, None]          # (B, S)
        np.testing.assert_allclose(
            out_np[rows], ref_np[rows], atol=2e-5
        )
        np.testing.assert_allclose(
            out_np[~rows], np.zeros_like(out_np[~rows]), atol=1e-6
        )
    else:
        np.testing.assert_allclose(out_np, ref_np, atol=2e-5)


def test_kv_bounds_grads_match_masked_reference():
    b, s = 2, 128
    q = _rand((b, s, 2, 64), 13)
    k = _rand((b, s, 2, 64), 14)
    v = _rand((b, s, 2, 64), 15)
    w = _rand((b, s, 2, 64), 16)
    lo = jnp.asarray([5, 0], jnp.int32)
    hi = jnp.asarray([128, 100], jnp.int32)
    mask = _window_mask(b, s, np.asarray(lo), np.asarray(hi))

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, kv_start=lo, kv_stop=hi,
                            block_q=128, block_kv=128) * w
        )

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, mask=mask) * w)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-5)


def test_bounded_scheduled_matches_rectangular(monkeypatch):
    """r3: the compressed dynamic-grid bounded path (default) must equal
    the rectangular pl.when path bit-for-bit on CPU (same block compute,
    different iteration) — fwd and grads, GQA, multi-block windows,
    including an empty-window row."""
    from mlcomp_tpu.ops.pallas import flash_attention as fa

    b, s = 4, 512
    q = _rand((b, s, 4, 64), 30)
    k = _rand((b, s, 2, 64), 31)
    v = _rand((b, s, 2, 64), 32)
    w = _rand((b, s, 4, 64), 33)
    lo = jnp.asarray([0, 64, 200, 70], jnp.int32)
    hi = jnp.asarray([512, 384, 200, 71], jnp.int32)  # row 2: EMPTY window

    def loss(q, k, v):
        return jnp.sum(
            fa.flash_attention(q, k, v, kv_start=lo, kv_stop=hi,
                               block_q=128, block_kv=128) * w
        )

    def run():
        out = fa.flash_attention(q, k, v, kv_start=lo, kv_stop=hi,
                                 block_q=128, block_kv=128)
        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return out, g

    monkeypatch.setenv("MLCOMP_FLASH_BOUNDED_SCHED", "0")
    out_rect, g_rect = run()
    monkeypatch.setenv("MLCOMP_FLASH_BOUNDED_SCHED", "1")
    out_sched, g_sched = run()
    np.testing.assert_array_equal(np.asarray(out_rect), np.asarray(out_sched))
    for a, b_ in zip(g_rect, g_sched):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
    # the empty-window row outputs exact zeros on both paths
    np.testing.assert_array_equal(
        np.asarray(out_sched[2]), np.zeros_like(np.asarray(out_sched[2]))
    )


def test_ragged_causal_scheduled_matches_rectangular(monkeypatch):
    """r3 late: causal + per-row windows (left-padded decode prefill) on
    the compressed dynamic grid must equal the rectangular causal path
    bit-for-bit — fwd and all three grads, GQA, including a row whose
    window∩causal intersection is empty for early q blocks."""
    from mlcomp_tpu.ops.pallas import flash_attention as fa

    b, s = 4, 512
    q = _rand((b, s, 4, 64), 40)
    k = _rand((b, s, 2, 64), 41)
    v = _rand((b, s, 2, 64), 42)
    w = _rand((b, s, 4, 64), 43)
    # lo = left-pad prefix; row 3's window starts past the first THREE
    # q blocks' causal reach (rows < 384 see no valid key at all)
    lo = jnp.asarray([0, 64, 200, 384], jnp.int32)
    hi = jnp.full((b,), s, jnp.int32)

    def loss(q, k, v):
        return jnp.sum(
            fa.flash_attention(q, k, v, causal=True, kv_start=lo,
                               kv_stop=hi, block_q=128, block_kv=128) * w
        )

    def run():
        out = fa.flash_attention(q, k, v, causal=True, kv_start=lo,
                                 kv_stop=hi, block_q=128, block_kv=128)
        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return out, g

    monkeypatch.setenv("MLCOMP_FLASH_BOUNDED_SCHED_CAUSAL", "0")
    out_rect, g_rect = run()
    monkeypatch.setenv("MLCOMP_FLASH_BOUNDED_SCHED_CAUSAL", "1")
    out_sched, g_sched = run()
    np.testing.assert_array_equal(np.asarray(out_rect), np.asarray(out_sched))
    for a, b_ in zip(g_rect, g_sched):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
    # rows before their window start see no keys: exact zeros
    np.testing.assert_array_equal(
        np.asarray(out_sched[3, :384]),
        np.zeros_like(np.asarray(out_sched[3, :384])),
    )


def test_kv_stop_only_right_padding():
    """kv_stop alone (BERT-style right padding) via the dispatch layer."""
    from mlcomp_tpu.ops.attention import dot_product_attention

    b, s = 2, 128
    q = _rand((b, s, 2, 64), 17)
    k = _rand((b, s, 2, 64), 18)
    v = _rand((b, s, 2, 64), 19)
    stop = jnp.asarray([128, 64], jnp.int32)
    out = dot_product_attention(q, k, v, kv_stop=stop)
    ref = reference_attention(
        q, k, v, mask=_window_mask(b, s, np.zeros(b, np.int64), np.asarray(stop))
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("bounded", [False, True])
def test_dispatch_under_mesh_is_a_shard_map_island(monkeypatch, bounded):
    """Under a dp x tp mesh the dispatch wraps the kernel in a shard_map
    island (the chip's compiler refuses a bare Mosaic call with sharded
    operands): outputs and grads match the reference, sharded inputs
    stay sharded."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mlcomp_tpu.ops.attention import dot_product_attention
    from mlcomp_tpu.parallel.mesh import MeshSpec, make_mesh, set_current_mesh

    monkeypatch.setenv("MLCOMP_TPU_FLASH", "1")
    mesh = make_mesh(MeshSpec(dp=2, tp=2), devices=jax.devices()[:4])
    set_current_mesh(mesh)
    try:
        rng = np.random.default_rng(0)
        sh = NamedSharding(mesh, P(("dp", "fsdp"), None, "tp", None))
        q, k, v = (
            jax.device_put(
                jnp.asarray(rng.standard_normal((2, 128, 4, 32)), jnp.float32),
                sh,
            )
            for _ in range(3)
        )
        kw = {"kv_stop": jnp.asarray([128, 100], jnp.int32)} if bounded else {}

        def loss(fn):
            return lambda q, k, v: fn(q, k, v, causal=True, **kw).sum()

        out = jax.jit(
            lambda q, k, v: dot_product_attention(q, k, v, causal=True, **kw)
        )(q, k, v)
        assert out.sharding.is_equivalent_to(sh, out.ndim)
        ref = reference_attention(q, k, v, causal=True, **kw)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
        g = jax.jit(jax.grad(loss(dot_product_attention), argnums=(0, 1, 2)))(
            q, k, v
        )
        g_ref = jax.grad(loss(reference_attention), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)
    finally:
        set_current_mesh(None)


@pytest.mark.parametrize("bounded", [False, True])
def test_backward_from_one_lane_lse_equals_the_128_lane_one(bounded):
    """The residual ``lse`` is one number a row, (B, H, S); broadcast back
    in ``_flash_bwd`` it gives, bit for bit, what the dq / dkv kernels
    gave from the forward kernel's own 128-lane buffer: the causal
    triangular grids, and a bounded window's scheduled grids."""
    from mlcomp_tpu.ops.pallas import flash_attention as fa

    b, h, s, d = 2, 2, 256, 128
    q, k, v, do = (_rand((b, h, s, d), 40 + i) for i in range(4))
    lo = jnp.asarray([5, 0], jnp.int32) if bounded else None
    hi = jnp.asarray([256, 130], jnp.int32) if bounded else None
    causal, scale, blk = not bounded, d ** -0.5, 128
    out, lse = fa._flash_fwd(q, k, v, lo, hi, scale, causal, blk, blk, True)
    assert lse.shape == (b, h, s, fa.LANES)

    _, res = fa._flash_vjp_fwd(q, k, v, lo, hi, scale, causal, blk, blk, True)
    assert res[-1].shape == (b, h, s)
    np.testing.assert_array_equal(res[-1], lse[..., 0])
    got = fa._flash_bwd(scale, causal, blk, blk, True, res, do)[:3]

    delta = jnp.sum(do * out, axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, fa.LANES))
    if bounded:
        want = fa._flash_bwd_bsched(scale, blk, blk, True, q, k, v, lo, hi,
                                    do, lse, delta, causal=False)[:3]
    else:
        want = fa._flash_bwd_tri(scale, blk, blk, True, q, k, v, do, lse,
                                 delta)[:3]
    for a, b_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


@pytest.mark.parametrize("h, h_kv, s, d, block_q, block_kv, with_g_lse, dtype", [
    (2, 2, 256, 128, 128, 128, False, jnp.float32),    # MHA
    (4, 2, 256, 128, 128, 128, False, jnp.float32),    # GQA, groups of 2
    (4, 1, 256, 128, 128, 128, False, jnp.float32),    # groups of 4
    (4, 2, 512, 128, 128, 256, False, jnp.float32),    # a KV block spans
    (4, 2, 512, 128, 256, 128, False, jnp.float32),    # ... and is spanned
    (4, 2, 256, 64, 128, 128, False, jnp.float32),     # lane-padded heads
    (4, 2, 256, 128, 128, 128, True, jnp.float32),     # an lse cotangent
    (4, 2, 256, 128, 128, 128, False, jnp.bfloat16),   # the train dtype
], ids=["mha", "gqa2", "gqa4", "bq128_bkv256", "bq256_bkv128", "d64",
        "g_lse", "bf16"])
def test_fused_backward_equals_the_two_kernel_backward(
    monkeypatch, h, h_kv, s, d, block_q, block_kv, with_g_lse, dtype
):
    """``_flash_bwd_tri``'s one kernel (dq resident in VMEM beside dk and
    dv) returns, bit for bit, what its dq kernel and its dk/dv kernel
    return: the same operands, and every sum in the same order."""
    from mlcomp_tpu.ops.pallas import flash_attention as fa

    b, scale = 2, d ** -0.5
    (q, k, v), _ = fa._kernel_layout(
        _rand((b, s, h, d), 60, dtype), _rand((b, s, h_kv, d), 61, dtype),
        _rand((b, s, h_kv, d), 62, dtype), d,
    )
    do = jnp.swapaxes(_rand((b, s, h, q.shape[-1]), 63, dtype), 1, 2)
    out, lse = fa._flash_fwd(q, k, v, None, None, scale, True, block_q,
                             block_kv, True)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), -1)
    if with_g_lse:
        delta = delta - _rand((b, h, s), 64)
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, fa.LANES))

    def backward():
        return fa._flash_bwd_tri(scale, block_q, block_kv, True, q, k, v,
                                 do, lse, delta)[:3]

    # a new function a trace: make_jaxpr remembers the one it has seen
    names = lambda: {c[1] for c in pallas_calls(  # noqa: E731
        jax.make_jaxpr(lambda: backward())().jaxpr)}
    assert names() == {"flash_dq_dkv_kernel_tri"}
    got = backward()
    monkeypatch.setattr(fa, "DQ_RESIDENT_BUDGET", 0)
    assert names() == {"flash_dq_kernel_tri", "flash_dkv_kernel_tri"}
    want = backward()
    for a, b_ in zip(got, want):
        assert a.dtype == b_.dtype == dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b_, np.float32))


@pytest.mark.parametrize("s, h, h_kv, kernels", [
    # rep x S x D x 4 B of float32 dq a KV head, against 4 MiB
    (2048, 4, 2, {"flash_dq_dkv_kernel_tri"}),                      # 2 MiB
    (4096, 4, 2, {"flash_dq_dkv_kernel_tri"}),                      # 4 MiB
    (4096, 4, 1, {"flash_dq_kernel_tri", "flash_dkv_kernel_tri"}),  # 8 MiB
], ids=["under", "at", "over"])
def test_backward_kernels_follow_the_resident_dq_budget(s, h, h_kv, kernels):
    """The causal backward is one kernel where a KV head's float32 dq
    fits ``DQ_RESIDENT_BUDGET`` and the dq + dk/dv pair beyond it: chosen
    from the shapes, read here off the gradient program's kernel names
    (nothing runs)."""
    qkv = [jax.ShapeDtypeStruct((1, s, n, 128), jnp.bfloat16)
           for n in (h, h_kv, h_kv)]

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    calls = pallas_calls(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(*qkv).jaxpr)
    assert {c[1] for c in calls} == kernels | {"flash_fwd_kernel_tri"}


@pytest.mark.parametrize("causal", [False, True])
def test_lse_cotangent_grads_match_reference(causal):
    """``flash_attention_lse``'s lse output is differentiable (ring
    attention's merge weights): its pair rule shares ``_flash``'s one-lane
    residual and the delta shift in ``_flash_bwd``."""
    from mlcomp_tpu.ops.pallas.flash_attention import flash_attention_lse

    b, s, h, d = 1, 128, 2, 64
    q, k, v, w = (_rand((b, s, h, d), 50 + i) for i in range(4))
    u = _rand((b, s, h), 54)

    def ref_pair(q, k, v):
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
        if causal:
            logits = jnp.where(jnp.tril(jnp.ones((s, s), bool)), logits, -1e30)
        lse = jax.nn.logsumexp(logits, axis=-1)            # (B, H, S)
        out = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(logits - lse[..., None]), v)
        return out, jnp.swapaxes(lse, 1, 2)

    def loss(fn):
        def f(q, k, v):
            out, lse = fn(q, k, v)
            return jnp.sum(out * w) + jnp.sum(lse * u)
        return f

    flash = lambda q, k, v: flash_attention_lse(  # noqa: E731
        q, k, v, causal=causal, block_q=128, block_kv=128)
    for a, b_ in zip(flash(q, k, v), ref_pair(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-5)
    gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(ref_pair), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-4)
