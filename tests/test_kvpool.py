"""Unit coverage for the device KV page pool (mlcomp_tpu/kvpool):
allocator free-list/ref-count bookkeeping, slot-row composition with
copy-on-write forks, the device prefix-page registry, the paged
layout's gather/scatter round trip (bit-exact on both cache families,
lax and Pallas-interpret gathers), and a fragmentation churn stress
asserting zero leaked pages at quiesce."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlcomp_tpu.kvpool import (
    GRAVE_PAGE,
    NULL_PAGE,
    RESERVED_PAGES,
    NoFreePages,
    PageAllocator,
    PagedLayout,
    PagePool,
)

# ------------------------------------------------------------ allocator


def test_allocator_lifecycle():
    a = PageAllocator(num_pages=10, page_tokens=4)
    assert a.total_pages == 8 and a.free_pages == 8
    got = a.alloc(3)
    assert len(got) == 3 and a.free_pages == 5 and a.used_pages == 3
    assert all(p >= RESERVED_PAGES for p in got)
    assert all(a.refs(p) == 1 for p in got)
    # retain/release ref-count: last release frees
    a.retain(got[0])
    assert a.refs(got[0]) == 2
    assert a.release(got[0]) is False
    assert a.release(got[0]) is True
    assert a.free_pages == 6
    a.check_invariants()
    # reserved pages are permanently pinned no-ops
    a.retain(NULL_PAGE)
    assert a.release(GRAVE_PAGE) is False
    # misuse raises instead of corrupting the books
    with pytest.raises(ValueError):
        a.release(got[0])  # already freed
    with pytest.raises(ValueError):
        a.retain(9)  # never allocated


def test_allocator_all_or_nothing():
    a = PageAllocator(num_pages=6, page_tokens=4)  # 4 allocatable
    a.alloc(3)
    free0 = a.free_pages
    with pytest.raises(NoFreePages):
        a.alloc(2)
    # the failed grab took NOTHING off the free list
    assert a.free_pages == free0
    assert a.counters["failed_allocs"] == 1
    a.check_invariants()


def test_allocator_lifo_reuse():
    a = PageAllocator(num_pages=8, page_tokens=4)
    (p,) = a.alloc(1)
    a.release(p)
    assert a.alloc(1) == [p]  # hottest page re-used first


# --------------------------------------------------------------- pool


def _pool(num_pages=18, page_tokens=4, l_buf=24, max_slots=4):
    class _Layout:  # geometry-only stand-in (no JAX)
        pass

    lay = _Layout()
    lay.num_pages = num_pages
    lay.page_tokens = page_tokens
    lay.max_pages = -(-l_buf // page_tokens)
    lay.page_bytes = lambda: 1024
    return PagePool(lay, max_slots=max_slots)


def test_slot_row_pads_cost_nothing():
    pool = _pool()
    # real span [10, 21): page 2 (8..12) .. page 5 (20..24) — pages 0-1
    # sit fully inside the pad prefix and stay NULL
    assert pool.pages_needed(10, 21) == 4
    row, mask, forks = pool.build_slot_row(10, 21)
    assert forks == 0
    assert list(row[:2]) == [NULL_PAGE, NULL_PAGE]
    assert all(p >= RESERVED_PAGES for p in row[2:6])
    assert list(row[6:]) == [NULL_PAGE] * (pool.max_pages - 6)
    assert list(mask[2:6]) == [True] * 4 and not mask[:2].any()
    pool.commit_slot_row(0, row)
    pool.check_invariants()
    pool.free_slot(0)
    assert pool.alloc.free_pages == pool.alloc.total_pages
    assert (pool.tables[0] == GRAVE_PAGE).all()
    pool.check_invariants()


def test_registry_share_and_cow_fork():
    pool = _pool()
    T = pool.page_tokens
    s_bucket, start_pad = 16, 6
    ids = list(range(100, 110))  # 10 real tokens
    row, mask, _ = pool.build_slot_row(start_pad, 21)
    pool.commit_slot_row(0, row)
    assert pool.registry_register(s_bucket, start_pad, ids, row) is True
    # same prompt again: idempotent (retry storm), no duplicate pin
    assert pool.registry_register(s_bucket, start_pad, ids, row) is False
    # a second request sharing the full prompt at the same placement
    lease = pool.registry_lookup(s_bucket, start_pad, ids)
    assert lease is not None and lease.matched == 10
    # boundary: shared span capped at the entry's page-aligned end
    assert lease.boundary == s_bucket
    row2, mask2, forks2 = pool.build_slot_row(start_pad, 21, shared=lease)
    # pages fully below the boundary are SHARED (same physical ids)
    n_shared = s_bucket // T - start_pad // T
    for p in range(start_pad // T, s_bucket // T):
        assert row2[p] == row[p] and not mask2[p]
        assert pool.alloc.refs(int(row[p])) >= 2
    assert forks2 == 0 and pool.counters["shared_mappings"] == n_shared
    pool.commit_slot_row(1, row2)
    lease.release()
    pool.check_invariants()
    # DIVERGENT suffix: matched stops mid-page -> the boundary page
    # forks a private copy (counted), earlier full pages still share
    ids3 = ids[:9] + [999]
    lease3 = pool.registry_lookup(s_bucket, start_pad, ids3)
    assert lease3 is not None and lease3.matched == 9
    # slot coords: shared boundary 6+9=15 lands inside page 3 (12..16)
    row3, mask3, forks3 = pool.build_slot_row(start_pad, 21, shared=lease3)
    assert forks3 == 1 and pool.alloc.counters["cow_forks"] == 1
    assert row3[2] == row[2]           # full page below 15: shared
    assert row3[3] != row[3] and mask3[3]  # the fork: private + written
    pool.release_row(row3)
    lease3.release()
    pool.check_invariants()


def test_registry_lru_reclaim_and_lease_pinning():
    pool = _pool(num_pages=18)
    rows = []
    for i in range(3):
        ids = [200 + 10 * i + j for j in range(10)]
        row, _, _ = pool.build_slot_row(6, 21)
        pool.commit_slot_row(i, row)
        pool.registry_register(16, 6, ids, row)
        rows.append((i, ids, row))
    for i, _, _ in rows:
        pool.free_slot(i)  # only registry pins remain
    pinned0 = pool.alloc.used_pages
    assert pinned0 > 0 and pool.reclaimable_pages() == pinned0
    # a LEASED entry survives reclaim even when its entry is evicted
    _, ids0, _ = rows[0]
    lease = pool.registry_lookup(16, 6, ids0)
    evicted = pool.reclaim_all()
    assert evicted == 3 and pool.registry_entries == 0
    assert pool.alloc.used_pages > 0  # the lease still pins its pages
    lease.release()
    assert pool.alloc.free_pages == pool.alloc.total_pages
    pool.check_invariants()


def test_pool_churn_no_leaks():
    """Fragmentation stress: random admit/retire cycles with sharing —
    at quiesce (slots freed, registry flushed) free == total."""
    pool = _pool(num_pages=40, max_slots=6)
    rng = np.random.RandomState(0)
    live = {}
    for step in range(300):
        if live and (len(live) == pool.max_slots or rng.rand() < 0.45):
            slot = rng.choice(sorted(live))
            lease = live.pop(slot)
            pool.free_slot(slot)
            if lease is not None:
                lease.release()
        else:
            slot = next(
                i for i in range(pool.max_slots) if i not in live
            )
            n_ids = int(rng.randint(1, 16))
            ids = rng.randint(0, 5, size=n_ids).tolist()  # collisions
            start_pad = 16 - n_ids
            lease = pool.registry_lookup(16, start_pad, ids)
            try:
                row, _, _ = pool.build_slot_row(
                    start_pad, 17 + int(rng.randint(0, 7)), shared=lease
                )
            except NoFreePages:
                pool.reclaim_all()
                if lease is not None:
                    lease.release()
                continue
            pool.commit_slot_row(slot, row)
            pool.registry_register(16, start_pad, ids, row)
            live[slot] = lease
        if step % 50 == 0:
            pool.check_invariants()
    for slot, lease in live.items():
        pool.free_slot(slot)
        if lease is not None:
            lease.release()
    pool.reclaim_all()
    pool.check_invariants()
    assert pool.alloc.free_pages == pool.alloc.total_pages
    st = pool.stats()
    assert st["pages_used"] == 0 and st["outstanding_page_leases"] == 0


# -------------------------------------------------------------- layout


@functools.lru_cache(maxsize=None)
def _cache_family(kv_quant):
    from mlcomp_tpu.models import create_model
    from mlcomp_tpu.models.generation import init_cache

    model = create_model({
        "name": "transformer_lm", "vocab_size": 64, "hidden": 64,
        "layers": 2, "heads": 2, "mlp_dim": 128, "dtype": "float32",
        "kv_quant": kv_quant,
    })
    return model, init_cache


@pytest.mark.parametrize("kv_quant", [False, True])
@pytest.mark.parametrize("impl", ["lax", "pallas"])
def test_layout_roundtrip_bit_exact(kv_quant, impl):
    """scatter -> gather through a page table rebuilds the EXACT dense
    cache pytree (shapes, dtypes, bytes) on both cache families, with
    both gather implementations (Pallas in interpret mode on CPU)."""
    from mlcomp_tpu.kvpool import layout as layout_mod

    model, init_cache = _cache_family(kv_quant)
    l_buf, slots, T = 24, 2, 8
    cache_abs = jax.eval_shape(lambda: init_cache(model, 1, l_buf))
    # page count unset at construction, then sized to a fully-private
    # table (the kv8 family lane-rounds the buffer, widening max_pages)
    lay = PagedLayout(cache_abs, l_buf, T)
    lay.num_pages = RESERVED_PAGES + slots * lay.max_pages
    # a fully-mapped private table (every row span = whole buffer)
    table = np.full((slots, lay.max_pages), GRAVE_PAGE, np.int32)
    nxt = RESERVED_PAGES
    for s in range(slots):
        for p in range(lay.max_pages):
            table[s, p] = nxt
            nxt += 1
    table = jnp.asarray(table)
    # a deterministic non-trivial dense cache: iota-patterned leaves
    dense = init_cache(model, slots, l_buf)
    dense = jax.tree.map(
        lambda leaf: (
            jnp.arange(leaf.size, dtype=jnp.float32)
            .reshape(leaf.shape).astype(leaf.dtype)
            if leaf.ndim else leaf
        ),
        dense,
    )
    pages = lay.fresh_pages()
    scalars = lay.scalars_of(dense)
    pages2 = lay.scatter(pages, table, dense)
    if impl == "pallas":
        # interpret-mode Pallas gather (the TPU kernel's logic on CPU)
        rebuilt_leaves = []
        for spec, pg in zip(lay.kv_specs, pages2):
            rows = layout_mod._gather_leaf_pallas(
                np.asarray(pg), table, interpret=True
            )
            rebuilt_leaves.append(
                lay._rows_to_view(spec, jnp.asarray(rows))
            )
        ki = iter(rebuilt_leaves)
        si = iter(scalars)
        rebuilt = lay.treedef.unflatten([
            next(ki) if s.slot_axis is not None else next(si)
            for s in lay.leaves
        ])
    else:
        rebuilt = lay.gather(pages2, table, scalars, impl="lax")
    flat_a = jax.tree_util.tree_leaves(dense)
    flat_b = jax.tree_util.tree_leaves(rebuilt)
    assert len(flat_a) == len(flat_b)
    for a, b in zip(flat_a, flat_b):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_layout_null_grave_semantics():
    """NULL-mapped positions gather zeros; a scatter through a table
    whose rows all map NULL/GRAVE leaves the zero page untouched for
    the content actually gathered from it (the structural invariant:
    every mapper writes back the zeros it read)."""
    model, init_cache = _cache_family(False)
    l_buf, T = 24, 8
    cache_abs = jax.eval_shape(lambda: init_cache(model, 1, l_buf))
    lay = PagedLayout(cache_abs, l_buf, T, num_pages=8)
    pages = lay.fresh_pages()
    table = jnp.full((1, lay.max_pages), NULL_PAGE, jnp.int32)
    dense = lay.gather(pages, table, lay.scalars_of(
        init_cache(model, 1, l_buf)
    ))
    for leaf in jax.tree_util.tree_leaves(dense):
        if leaf.ndim:
            assert not np.asarray(leaf).any()
    # round-trip the zeros: NULL stays all-zero
    pages2 = lay.scatter(pages, table, dense)
    for pg in pages2:
        assert not np.asarray(pg[NULL_PAGE]).any()


def test_layout_page_tokens_must_divide():
    model, init_cache = _cache_family(False)
    cache_abs = jax.eval_shape(lambda: init_cache(model, 1, 24))
    lay = PagedLayout(cache_abs, 24, 5, num_pages=12)
    # geometry only: max_pages covers the longest leaf buffer
    assert lay.max_pages >= -(-24 // 5)
    with pytest.raises(ValueError):
        PagedLayout(cache_abs, 24, 0, num_pages=12)


def test_build_slot_row_alloc_end_defers_decode_pages():
    """Lazy decode allocation: ``alloc_end`` bounds the pages built
    NOW (the tail stays NULL), and ``extend_slot_row`` grows the
    committed row all-or-nothing as the cursor approaches."""
    pool = _pool(num_pages=18, page_tokens=4, l_buf=24)
    # span [10, 21) = pages 2..5; alloc_end 17 backs only pages 2..4
    row, mask, _ = pool.build_slot_row(10, 21, alloc_end=17)
    assert all(p >= RESERVED_PAGES for p in row[2:5])
    assert row[5] == NULL_PAGE and not mask[5]
    pool.commit_slot_row(0, row)
    used0 = pool.alloc.used_pages
    row2 = pool.extend_slot_row(0, 5, 6)
    assert row2[5] >= RESERVED_PAGES
    assert pool.alloc.used_pages == used0 + 1
    assert (pool.tables[0] == row2).all()
    pool.check_invariants()
    # exhaustion is all-or-nothing: a failed extend changes nothing
    pool.alloc.alloc(pool.alloc.free_pages)
    with pytest.raises(NoFreePages):
        pool.extend_slot_row(0, 0, 1)  # pos 0 is NULL (pad prefix)
    assert pool.tables[0][0] == NULL_PAGE
    # private_pages_needed honors the same bound
    assert pool.private_pages_needed(10, 21, alloc_end=17) == 3
    assert pool.private_pages_needed(10, 21) == 4


def test_paged_kernel_bit_exact_vs_dense():
    """The paged Pallas kernel (interpret mode) against the dense
    kernel on the same cache bytes scattered into pages through a
    permuted table: BIT-exact, with NULL pages outside the windows
    skipped and unmapped pages poisoned with NaN scale bytes (a
    skipped page's garbage must never reach the accumulator — the
    interpret-mode unit that catches in-kernel DMA/masking bugs the
    engine matrix would only surface as diverged tokens).  The dense
    single-token kernel walks granules of the paged kernel's block by
    default: a row whose window touches every lane block of it is
    moved and attended whole (the same partition of the online
    softmax: bit-exact).  A row whose trip is trimmed to the blocks
    its window touches (PR 36) reduces over fewer columns than the
    paged kernel's block, so the partition is no longer the same by
    construction: that row, and a walk handed a thinner granule, agree
    to float rounding.  Paged against paged (NULL pages, NaN-poisoned
    unmapped pages) stays exact."""
    from mlcomp_tpu.ops.pallas.decode_attention import (
        decode_attention,
        paged_block_kv,
        paged_decode_attention,
        quantize_kv,
    )

    rng = np.random.RandomState(0)
    B, H, HKV, DH, L, T = 2, 8, 4, 128, 1024, 32
    MP = L // T
    assert paged_block_kv(L, HKV, DH, T) == L
    lo_hi = ((5, 900), (40, 41))
    k8, ks = quantize_kv(jnp.asarray(
        rng.randn(B, HKV, L, DH).astype(np.float32)
    ))
    v8, vs = quantize_kv(jnp.asarray(
        rng.randn(B, HKV, L, DH).astype(np.float32)
    ))
    ks4 = ks[:, :, None, :].astype(jnp.bfloat16)
    vs4 = vs[:, :, None, :].astype(jnp.bfloat16)
    start = jnp.asarray(np.array([lo for lo, _ in lo_hi], np.int32))
    # pages: permuted physical placement; UNMAPPED pages poisoned
    P = RESERVED_PAGES + B * MP
    perm = rng.permutation(B * MP)
    table = np.zeros((B, MP), np.int32)
    kqp = np.zeros((P, HKV, T, DH), np.int8)
    vqp = np.zeros((P, HKV, T, DH), np.int8)
    ksp = np.full((P, HKV, 1, T), np.nan, np.float32)
    vsp = np.full((P, HKV, 1, T), np.nan, np.float32)
    k8n, v8n = np.asarray(k8), np.asarray(v8)
    ks4n = np.asarray(ks4.astype(jnp.float32))
    vs4n = np.asarray(vs4.astype(jnp.float32))
    for b in range(B):
        for p in range(MP):
            pid = RESERVED_PAGES + int(perm[b * MP + p])
            table[b, p] = pid
            kqp[pid] = k8n[b, :, p * T:(p + 1) * T, :]
            vqp[pid] = v8n[b, :, p * T:(p + 1) * T, :]
            ksp[pid] = ks4n[b, :, :, p * T:(p + 1) * T]
            vsp[pid] = vs4n[b, :, :, p * T:(p + 1) * T]
    pages = (jnp.asarray(kqp), jnp.asarray(ksp).astype(jnp.bfloat16),
             jnp.asarray(vqp), jnp.asarray(vsp).astype(jnp.bfloat16))
    q = jnp.asarray(rng.randn(B, H, DH).astype(np.float32))
    stop = jnp.asarray(np.array([hi for _, hi in lo_hi], np.int32))
    dense = decode_attention(
        q, k8, ks4, v8, vs4, kv_start=start, kv_stop=stop
    )
    np.testing.assert_allclose(
        np.asarray(decode_attention(
            q, k8, ks4, v8, vs4, kv_start=start, kv_stop=stop,
            block_kv=256,
        )),
        np.asarray(dense), atol=1e-5,
    )
    # NULL out every page fully outside the window: the kernel
    # must skip them (no DMA) and still match
    tbl2 = table.copy()
    for b, (lo, hi) in enumerate(lo_hi):
        for p in range(MP):
            if (p + 1) * T <= lo or p * T >= hi:
                tbl2[b, p] = NULL_PAGE
    paged_null = paged_decode_attention(
        q, *pages, jnp.asarray(tbl2), kv_start=start, kv_stop=stop
    )
    paged = paged_decode_attention(
        q, *pages, jnp.asarray(table), kv_start=start, kv_stop=stop
    )
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(paged_null))
    # row 0, [5, 900), touches all eight lane blocks of the 1024-token
    # granule; row 1, [40, 41), one of them
    np.testing.assert_array_equal(np.asarray(dense[0]), np.asarray(paged[0]))
    np.testing.assert_allclose(
        np.asarray(dense[1]), np.asarray(paged[1]), atol=1e-5
    )


def test_insert_rows_routes_shared_to_grave():
    """insert_rows writes ONLY write-selected pages; entries routed to
    GRAVE (shared/NULL positions) leave their physical pages alone —
    the copy-on-write mapping is zero-copy by construction."""
    model, init_cache = _cache_family(False)
    l_buf, T = 24, 8
    cache_abs = jax.eval_shape(lambda: init_cache(model, 1, l_buf))
    lay = PagedLayout(cache_abs, l_buf, T, num_pages=10)
    pages = lay.fresh_pages()
    # pre-mark page 2 (the "shared prefix" page) with a sentinel
    pages = [pg.at[2].set(7.0) if pg.dtype == jnp.float32 else
             pg.at[2].set(7) for pg in pages]
    row = init_cache(model, 1, l_buf)
    row = jax.tree.map(
        lambda leaf: jnp.ones(leaf.shape, leaf.dtype)
        if leaf.ndim else leaf, row,
    )
    # slot maps [shared=2, private=3, private=4]; write_sel routes the
    # shared page to GRAVE
    wsel = jnp.asarray(np.array([GRAVE_PAGE, 3, 4], np.int32))
    out = lay.insert_rows(pages, wsel, row)
    for pg in out:
        sent = np.asarray(pg[2]).ravel()[0]
        assert sent == 7  # shared page untouched
        assert np.asarray(pg[3]).any()  # private pages got the bytes
        assert not np.asarray(pg[NULL_PAGE]).any()
