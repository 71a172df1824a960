"""Paged layout over the engine's KV cache pytree: TRACED gather and
scatter between page arrays and the dense ``(slots, l_buf, ...)`` view,
plus the geometry the fused paged-attention path reads pages through.

The design constraint is BIT-EQUALITY with the dense layout, by two
routes:

- the LAX REFERENCE path gathers the dense view through the slot page
  tables, runs the UNCHANGED dispatch core on it, and scatters the
  updated view back — pure data movement (pad/reshape/moveaxis/take/
  scatter — no arithmetic), exact for every dtype the cache families
  use (f32/bf16 K/V, int8 kv8 blocks, bf16 scales);
- the FUSED path (``kvpool/attn.py`` + the paged Pallas kernels in
  ``ops/pallas/decode_attention.py``) never materializes the dense
  view: the decode kernels DMA pages straight from the pool arrays,
  block-index-from-prefetched-table, and the per-token K/V append
  scatters into its page in place.  Bit-equality there comes from the
  PAGE SHAPE: a page is a dense-layout tile.

Layout rules, shared with the host prefix cache
(``cache/kv_store.SLOT_AXES``): every KV leaf has a batch (slot) axis 0
and a sequence (cache-slot) axis.  Its page array drops the batch axis,
puts the physical-page axis first, and shrinks the sequence axis to
``page_tokens`` IN PLACE — e.g. a dense ``(S, Hkv, L, dh)`` kv8 leaf
pages as ``(num_pages, Hkv, T, dh)``.  Keeping the dense axis order is
what lets the fused attention kernels copy a page into a dense-shaped
VMEM block with no in-kernel transpose, so the fused compute runs the
EXACT math (same block partition, same accumulation order) as the dense
kernel.  Non-KV leaves (``cache_index`` scalars) are
slot-count-independent and ride the paged carry untouched.

The reference gather has two implementations:

- ``lax``: ``jnp.take`` over the page axis — runs everywhere, the
  correctness reference (CPU tests run this path);
- ``pallas``: a scalar-prefetch DMA copy kernel
  (``PrefetchScalarGridSpec``; the page table is prefetched so each
  grid step's block index comes straight from it) — one HBM pass with
  no intermediate index materialization.  TPU only; ``impl="auto"``
  picks it there and falls back to ``lax`` elsewhere.

Whether any of this runs at all is the engine's
``MLCOMP_TPU_PAGED_ATTN`` knob: ``lax`` keeps the gather/scatter
sandwich as the everywhere-reference, everything else reads K/V
through the page table directly and this module's gather/scatter serve
only the reference/bisect path (see docs/serving.md).
"""

from __future__ import annotations

from typing import Any, List, NamedTuple, Optional, Sequence, Tuple


class LeafSpec(NamedTuple):
    keystr: str
    slot_axis: Optional[int]   # None: non-KV leaf (cache_index scalar)
    shape: Tuple[int, ...]     # dense leaf shape at slots=1
    dtype: Any
    seq_len: int               # the leaf's OWN buffer length: the kv8
    # family lane-rounds L past the engine's l_buf (pick_buffer_len);
    # the rounded tail is never written non-zero, so its pages stay
    # NULL — but gather/scatter must cover it to rebuild exact shapes


class PagedLayout:
    """Static description of one engine cache family's paged form.

    Built once from an ABSTRACT ``init_cache(model, 1, l_buf)`` pytree
    (shapes only — nothing materializes); every traced gather/scatter
    closes over it, so the treedef and per-leaf axes never ride the
    program arguments.
    """

    def __init__(self, cache, l_buf: int, page_tokens: int,
                 num_pages: Optional[int] = None):
        import jax

        from mlcomp_tpu.cache.kv_store import SLOT_AXES, _leaf_name

        self.l_buf = int(l_buf)
        self.page_tokens = int(page_tokens)
        # num_pages may stay unset while the caller derives the pool
        # budget FROM the layout (max_pages is a function of the cache
        # shapes alone) — anything that materializes or prices pages
        # checks it via _require_pages
        self.num_pages = None if num_pages is None else int(num_pages)
        if self.page_tokens < 1:
            raise ValueError(f"page_tokens must be >= 1: {page_tokens}")
        flat, self.treedef = jax.tree_util.tree_flatten_with_path(cache)
        self.leaves: List[LeafSpec] = []
        for path, leaf in flat:
            name = _leaf_name(path)
            keystr = "/".join(_leaf_name((k,)) for k in path)
            if name == "cache_index":
                self.leaves.append(
                    LeafSpec(keystr, None, tuple(leaf.shape), leaf.dtype,
                             0)
                )
                continue
            if name not in SLOT_AXES:
                raise ValueError(
                    f"unknown cache leaf {name!r}: teach "
                    "cache/kv_store.py its slot axis before paging "
                    "this layout"
                )
            ax = SLOT_AXES[name]
            if leaf.shape[ax] < self.l_buf:
                raise ValueError(
                    f"leaf {keystr} has {leaf.shape[ax]} cache slots at "
                    f"axis {ax}, below l_buf={self.l_buf}"
                )
            self.leaves.append(
                LeafSpec(keystr, ax, tuple(leaf.shape), leaf.dtype,
                         int(leaf.shape[ax]))
            )
        self.kv_specs = [s for s in self.leaves if s.slot_axis is not None]
        # fused-attention lookup: leaf keystr -> kv_specs index (the
        # attention modules resolve their own cache leaves by path)
        self.kv_index = {s.keystr: i for i, s in enumerate(self.kv_specs)}
        # table width: enough pages to cover the LONGEST leaf buffer
        # (the kv8 family lane-rounds past l_buf); each leaf gathers
        # through only its own first ceil(seq_len/T) table columns, and
        # pages past a slot's token span are NULL, so the rounded tail
        # costs table entries, never pages
        self.max_pages = max(
            -(-s.seq_len // self.page_tokens) for s in self.kv_specs
        )

    # ---------------------------------------------------------- allocation

    def _require_pages(self) -> int:
        if self.num_pages is None:
            raise ValueError(
                "PagedLayout.num_pages is unset: set it (or pass it at "
                "construction) before materializing or pricing pages"
            )
        return self.num_pages

    def page_shape(self, spec: LeafSpec) -> Tuple[int, ...]:
        # a page is a dense-layout TILE: drop the batch axis, put the
        # physical-page axis first, shrink the sequence axis to T in
        # place — the fused kernels DMA a page into a dense-shaped
        # VMEM block with no transpose
        return (self._require_pages(),) + self._page_rest(spec)

    def _page_rest(self, spec: LeafSpec) -> Tuple[int, ...]:
        return tuple(
            self.page_tokens if i == spec.slot_axis else d
            for i, d in enumerate(spec.shape) if i != 0
        )

    def fresh_pages(self) -> List[Any]:
        """Zeroed device page arrays, one per KV leaf (kv order)."""
        import jax.numpy as jnp

        return [
            jnp.zeros(self.page_shape(s), s.dtype) for s in self.kv_specs
        ]

    def page_bytes(self) -> int:
        """Bytes of ONE page across every KV leaf — the allocation
        quantum admission control budgets in.  Independent of
        num_pages, so the caller can size the pool FROM it."""
        import numpy as np

        total = 0
        for s in self.kv_specs:
            total += (
                int(np.prod(self._page_rest(s), dtype=np.int64))
                * np.dtype(s.dtype).itemsize
            )
        return total

    def bytes_total(self) -> int:
        return self.page_bytes() * self._require_pages()

    def dense_view_bytes(self, slots: int) -> int:
        """Bytes of the DENSE view at ``slots`` rows — what the lax
        reference path materializes (and moves) per gather/scatter,
        and the honest per-forward KV read of a dense-layout engine."""
        import numpy as np

        total = 0
        for s in self.kv_specs:
            total += (
                int(np.prod(s.shape[1:], dtype=np.int64))
                * np.dtype(s.dtype).itemsize
            )
        return total * int(slots)

    # ------------------------------------------------------------- tracing

    def _from_view(self, spec: LeafSpec, leaf):
        """Dense leaf -> (S, MP, *page_rest) page tiles, zero-padded
        from the leaf's seq_len up to MP*T (the pad lands beyond every
        slot's span, on pages whose gathered content was zero — see
        scatter)."""
        import jax.numpy as jnp

        ax = spec.slot_axis
        T = self.page_tokens
        pad = self.max_pages * T - spec.seq_len
        if pad:
            widths = [(0, 0)] * leaf.ndim
            widths[ax] = (0, pad)
            leaf = jnp.pad(leaf, widths)
        shape = (
            leaf.shape[:ax] + (self.max_pages, T) + leaf.shape[ax + 1:]
        )
        return jnp.moveaxis(leaf.reshape(shape), ax, 1)

    def _rows_to_view(self, spec: LeafSpec, rows,
                      width: Optional[int] = None):
        """(S, n_cols, *page_rest) gathered page tiles -> the dense
        leaf layout, sliced to ``width`` slots (default: the LEAF's
        own buffer length — the kv8 family lane-rounds past l_buf, and
        each leaf rebuilds exactly the shape the model allocated;
        registry-hit span gathers pass their chunk-aligned prefix
        width instead)."""
        import jax.numpy as jnp

        ax = spec.slot_axis
        T = self.page_tokens
        n_cols = rows.shape[1]
        rows = jnp.moveaxis(rows, 1, ax)   # (S, d1.., n_cols, T, .., dn)
        shape = rows.shape[:ax] + (n_cols * T,) + rows.shape[ax + 2:]
        rows = rows.reshape(shape)
        index = [slice(None)] * rows.ndim
        index[ax] = slice(0, spec.seq_len if width is None else width)
        return rows[tuple(index)]

    def gather_leaf(self, spec: LeafSpec, pages, table, impl: str = "lax"):
        """TRACED: ONE leaf's dense view through ``table`` — the unit
        the reference gather and the fused path's per-layer lax reads
        (non-quant family, ineligible geometries) share."""
        n_cols = -(-spec.seq_len // self.page_tokens)
        rows = _gather_leaf(pages, table[:, :n_cols], impl=impl)
        return self._rows_to_view(spec, rows)

    def gather(self, pages: Sequence[Any], table, scalars: Sequence[Any],
               impl: str = "auto"):
        """TRACED: rebuild the dense cache pytree from page arrays
        through ``table`` (S, max_pages) int32.  ``scalars`` are the
        non-KV leaves in layout order.  The lax REFERENCE path — the
        fused attention path never calls this on the hot path."""
        views, ki, si = [], 0, 0
        for spec in self.leaves:
            if spec.slot_axis is None:
                views.append(scalars[si])
                si += 1
                continue
            # only this leaf's own columns: pages past ceil(seq_len/T)
            # map NULL for every slot (the table is sized to the
            # LONGEST leaf), so gathering them would move zeros the
            # _rows_to_view slice discards anyway
            views.append(
                self.gather_leaf(spec, pages[ki], table, impl=impl)
            )
            ki += 1
        return self.treedef.unflatten(views)

    def scatter(self, pages: Sequence[Any], table, cache) -> List[Any]:
        """TRACED: write the dense view back through ``table``.  Every
        mapped page receives the bytes the view holds for it; shared
        pages get identical bytes from every mapper (decode never
        writes below a slot's private span — the COW alloc policy in
        pool.py guarantees it), NULL_PAGE gets back the zeros it
        served, GRAVE_PAGE absorbs retired rows' frozen-cursor writes.
        """
        import jax

        flat, _ = jax.tree_util.tree_flatten_with_path(cache)
        dense = [leaf for _, leaf in flat]
        out, ki = [], 0
        S = table.shape[0]
        flat_tbl = table.reshape((S * self.max_pages,))
        for spec, leaf in zip(self.leaves, dense):
            if spec.slot_axis is None:
                continue
            rows = self._from_view(spec, leaf)
            rows = rows.reshape(
                (S * self.max_pages,) + rows.shape[2:]
            )
            out.append(pages[ki].at[flat_tbl].set(rows))
            ki += 1
        return out

    def scalars_of(self, cache) -> List[Any]:
        """The non-KV leaves of a dense cache pytree, layout order."""
        import jax

        flat, _ = jax.tree_util.tree_flatten_with_path(cache)
        return [
            leaf for (path, leaf), spec in zip(flat, self.leaves)
            if spec.slot_axis is None
        ]

    def insert_rows(self, pages: Sequence[Any], write_sel,
                    cache) -> List[Any]:
        """TRACED: write ONE prefilled ``(1, ...)`` dense admission
        cache into the page arrays.  ``write_sel`` is the slot's
        (max_pages,) int32 write ROUTING: the private page id where the
        insert must materialize the row's bytes, ``GRAVE_PAGE``
        everywhere else — shared prefix pages keep their bytes (the
        copy-on-write mapping: the admission recomputed identical
        bytes, and routing them to the graveyard is what makes the
        shared page a zero-copy reference), NULL stays untouched, and
        LAZY decode pages (allocated later, as the cursor approaches)
        receive nothing here because they do not exist yet.  Duplicate
        GRAVE targets are fine: the graveyard's content is never
        read."""
        import jax

        flat, _ = jax.tree_util.tree_flatten_with_path(cache)
        dense = [leaf for _, leaf in flat]
        out, ki = [], 0
        for spec, leaf in zip(self.leaves, dense):
            if spec.slot_axis is None:
                continue
            rows = self._from_view(spec, leaf)[0]  # (MP, *page_rest)
            out.append(pages[ki].at[write_sel].set(rows))
            ki += 1
        return out

    def gather_row_span(self, pages: Sequence[Any], page_ids,
                        width: int) -> List[Any]:
        """TRACED: slot rows [0, width) of every KV leaf as ONE (1,...)
        row set (``cache/kv_store.write_slot_rows`` order) gathered
        from ``page_ids`` (the span's table entries, device int32) —
        the device-to-device half of a prefix-registry hit: no host
        round-trip, the persistent pages stay shared."""
        out = []
        for spec, pg in zip(self.kv_specs, pages):
            rows = pg[page_ids][None]    # (1, n_pages, *page_rest)
            out.append(self._rows_to_view(spec, rows, width=width))
        return out


def _gather_leaf(pages, table, impl: str = "auto"):
    """(P, *page_rest) pages + (S, MP) table -> (S, MP, *page_rest).

    ``impl``: "lax" (jnp.take — everywhere), "pallas" (TPU DMA-copy
    kernel), "auto" (pallas on TPU, else lax).
    """
    import jax.numpy as jnp

    if impl == "auto":
        from mlcomp_tpu.ops.pallas import on_tpu

        impl = "pallas" if on_tpu() else "lax"
    if impl == "lax":
        return jnp.take(pages, table, axis=0)
    if impl != "pallas":
        raise ValueError(f"impl must be auto/lax/pallas, got {impl!r}")
    return _gather_leaf_pallas(pages, table)


def _gather_leaf_pallas(pages, table, interpret: bool = False):
    """Scalar-prefetch page gather: grid (S, MP); the prefetched table
    drives each step's input block index, so block (s, p) DMA-copies
    physical page ``table[s, p]`` into logical position (s, p) — one
    HBM pass, no index arrays materialized.  Collapses the per-page
    payload to one flat axis so the same kernel serves every leaf
    family (bf16 K/V, int8 kv8 blocks, bf16 scales) whatever the
    dense-order page tile looks like — the copy never cares about the
    inner layout."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    P = pages.shape[0]
    rest = pages.shape[1:]
    R = 1
    for d in rest:
        R *= d
    S, MP = table.shape
    # the payload rides as whole (rows, 128) lane tiles: the TPU
    # lowering refuses a (1, R) block over a (P, R) array (its last
    # two block dims must be (8, 128)-divisible or span the array's),
    # while a block spanning the trailing (rows, lanes) dims is legal
    # for every leaf family.  Payloads that are not a lane multiple
    # keep one row.
    tile = (R // 128, 128) if R % 128 == 0 else (1, R)
    pages3 = pages.reshape((P,) + tile)

    def copy_kernel(tbl_ref, page_ref, out_ref):
        # blocks: page_ref (1, *tile) at physical page tbl[s, p],
        # out_ref (1, 1, *tile) at logical (s, p) — a pure DMA copy
        out_ref[0, 0] = page_ref[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S, MP),
        in_specs=[
            pl.BlockSpec((1,) + tile, lambda s, p, tbl: (tbl[s, p], 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1) + tile, lambda s, p, tbl: (s, p, 0, 0)
        ),
    )
    out = pl.pallas_call(
        copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, MP) + tile, pages.dtype),
        interpret=interpret,
        name="page_gather",
    )(table, pages3)
    return out.reshape((S, MP) + rest)
