"""Quantization-aware host KV-block storage for the prefix cache.

The device half of ``mlcomp_tpu/cache``: prefix_index.py decides WHAT
is cached; this module knows WHERE the K/V rows live inside the
engine's per-layer cache leaves and how to move them host<->device
without breaking the engine's per-row cursor/start/kv_mask contract
(``engine.py`` ``_Slot``, ``models/transformer.py`` ``_decode_attention``).

Layouts handled (leaf name -> slot axis), matching both cache families
``models/transformer.py`` allocates:

- bf16/f32 cache: ``cached_key`` / ``cached_value`` (B, L, Hkv, dh),
  slot axis 1;
- int8 kv8 cache: ``cached_key_q`` / ``cached_value_q``
  (B, Hkv, L, dhp) int8 at slot axis 2, plus ``cached_key_scale`` /
  ``cached_value_scale`` (B, Hkv, 1, L) bf16 at slot axis 3;
- latent cache: ``cached_latent`` (B, L, width), slot axis 1, no head
  axis (``models/latent_attention.py``).

``cache_index`` is the one non-KV cache leaf; it is engine-owned and
never captured.

Why token-indexed blocks transplant across requests at all: a cached
row holds K/V AFTER RoPE, and the serving path's LEFT-pad contract
(``engine.left_pad_row`` + cumsum positions) gives real token j position
j regardless of bucket or pad width — so row j of a prefix is the same
bytes wherever the prefix lands, and inserting it at the new request's
``start_pad + j`` slot is exact.  Captured rows round-trip device ->
numpy -> device bit-identically (f32/bf16/int8 storage, no re-quant),
which is what makes cache-hit outputs EQUAL to cold prefill, not just
close.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

# leaf name -> axis holding the cache slot (sequence) dimension
SLOT_AXES = {
    "cached_key": 1,
    "cached_value": 1,
    "cached_key_q": 2,
    "cached_value_q": 2,
    "cached_key_scale": 3,
    "cached_value_scale": 3,
    # models/latent_attention.py: one latent a token for all heads
    "cached_latent": 1,
}

# leaf name -> axis holding the KV-head dimension — the axis sharded
# over the tp mesh axis when the engine serves sharded (the Megatron
# K/V projections are head-sharded, so head-sharded cache bytes is
# what XLA propagation picks; the paged layout pins it EXPLICITLY on
# its page arrays so donation keeps a stable sharding).  Page arrays
# keep the dense axis order minus the batch axis plus a leading page
# axis, so the index is the same in both layouts.
HEAD_AXES = {
    "cached_key": 2,
    "cached_value": 2,
    "cached_key_q": 1,
    "cached_value_q": 1,
    "cached_key_scale": 1,
    "cached_value_scale": 1,
}


def _leaf_name(path) -> str:
    key = path[-1]
    return getattr(key, "key", str(key))


def kv_leaf_items(cache) -> List[Tuple[str, int, Any]]:
    """Deterministic (keystr, slot_axis, leaf) list over a cache pytree
    — the canonical order every capture/assemble/write call shares.
    Unknown leaf names (a new cache layout) fail loudly rather than
    silently caching garbage."""
    import jax

    items = []
    flat, _ = jax.tree_util.tree_flatten_with_path(cache)
    for path, leaf in flat:
        name = _leaf_name(path)
        if name == "cache_index":
            continue
        if name not in SLOT_AXES:
            raise ValueError(
                f"unknown cache leaf {name!r}: teach cache/kv_store.py "
                "its slot axis before prefix-caching this layout"
            )
        keystr = "/".join(_leaf_name((k,)) for k in path)
        items.append((keystr, SLOT_AXES[name], leaf))
    return items


def slice_slot_rows(cache, lo: int, hi: int):
    """TRACED: slot rows [lo, hi) of every KV leaf, in
    ``kv_leaf_items`` order.  lo/hi are STATIC and chunk-quantized by
    the engine, so the program count stays bounded per bucket (a
    dynamic prompt-length slice would recompile per length) while a
    cache-hit admission captures only the rows its suffix chunks
    actually recomputed — not the whole bucket."""
    out = []
    for _, axis, leaf in kv_leaf_items(cache):
        idx = [slice(None)] * leaf.ndim
        idx[axis] = slice(lo, hi)
        out.append(leaf[tuple(idx)])
    return tuple(out)


def write_slot_rows(cache, rows, width: int):
    """TRACED: write ``rows`` (``slice_slot_rows`` order, slot width
    ``width``) into slots [0, width) of every KV leaf.  Callers fill
    only the real prefix span; the zero filler lands on pad slots
    (masked by kv_mask) or slots the suffix chunks rewrite before any
    read."""
    import jax

    items = kv_leaf_items(cache)
    assert len(items) == len(rows), (len(items), len(rows))
    updates = {}
    for (keystr, axis, leaf), row in zip(items, rows):
        idx = [slice(None)] * leaf.ndim
        idx[axis] = slice(0, width)
        updates[keystr] = leaf.at[tuple(idx)].set(row.astype(leaf.dtype))

    def rebuild(path, leaf):
        keystr = "/".join(_leaf_name((k,)) for k in path)
        return updates.get(keystr, leaf)

    return jax.tree_util.tree_map_with_path(rebuild, cache)


class KVBlock:
    """Host copy of per-layer K/V rows for ``ntokens`` consecutive
    prefix tokens: ``{keystr: np.ndarray}`` keeping each leaf's full
    shape except the slot axis, which is the token count.  The ONLY
    methods the prefix index calls are ``slice``/``ntokens``/``nbytes``
    — keep that protocol in sync with tools/cachecheck.py's FakeBlock.
    """

    __slots__ = ("arrays", "axes", "ntokens", "nbytes")

    def __init__(self, arrays: Dict[str, np.ndarray], axes: Dict[str, int],
                 ntokens: int):
        self.arrays = arrays
        self.axes = axes
        self.ntokens = int(ntokens)
        self.nbytes = int(sum(a.nbytes for a in arrays.values()))

    def slice(self, start: int, stop: int) -> "KVBlock":
        """Tokens [start, stop) as a new block, MATERIALIZED (the trie's
        edge splits call this; a view would keep the whole parent buffer
        alive and make eviction accounting a lie).  Leases never slice —
        ``assemble_prefix_rows`` reads ``arrays`` directly with a
        per-segment take count."""
        out = {}
        for k, a in self.arrays.items():
            idx = [slice(None)] * a.ndim
            idx[self.axes[k]] = slice(start, stop)
            out[k] = np.ascontiguousarray(a[tuple(idx)])
        return KVBlock(out, dict(self.axes), stop - start)


def block_from_capture(rows, keys_axes: List[Tuple[str, int]],
                       start: int, n_tokens: int) -> KVBlock:
    """Trim captured host rows (slot span starting wherever the engine
    sliced) to the ``n_tokens`` real-token rows beginning at index
    ``start`` WITHIN the capture, and wrap as a KVBlock."""
    arrays, axes = {}, {}
    for (keystr, axis), arr in zip(keys_axes, rows):
        a = np.asarray(arr)
        idx = [slice(None)] * a.ndim
        idx[axis] = slice(start, start + n_tokens)
        arrays[keystr] = np.ascontiguousarray(a[tuple(idx)])
        axes[keystr] = axis
    return KVBlock(arrays, axes, n_tokens)


def assemble_prefix_rows(segments, keys_axes: List[Tuple[str, int]],
                         width: int, start_pad: int,
                         n_tokens: int) -> List[np.ndarray]:
    """Host rows of slot width ``width`` (``write_slot_rows`` order)
    with the lease's first ``n_tokens`` cached tokens placed at slots
    [start_pad, start_pad + n_tokens) and zeros on the pad prefix.
    ``width`` is the engine's chunk-aligned hit boundary, so the
    host->device upload moves only the prefix span, not the bucket."""
    first_block = segments[0][0]
    out = []
    for keystr, axis in keys_axes:
        proto = first_block.arrays[keystr]
        shape = list(proto.shape)
        shape[axis] = width
        buf = np.zeros(shape, proto.dtype)
        at = start_pad
        left = n_tokens
        for block, take in segments:
            if left <= 0:
                break
            take = min(take, left)
            src = block.arrays[keystr]
            sidx = [slice(None)] * src.ndim
            sidx[axis] = slice(0, take)
            didx = [slice(None)] * buf.ndim
            didx[axis] = slice(at, at + take)
            buf[tuple(didx)] = src[tuple(sidx)]
            at += take
            left -= take
        assert left == 0, (n_tokens, "lease shorter than requested span")
        out.append(buf)
    return out


class PrefixKVCache:
    """The engine-facing facade: PrefixIndex + layout glue + counters.

    One instance serves ONE engine (the block layout is the engine's
    cache layout); the engine loop thread calls lookup/insert_async,
    HTTP threads read ``stats()`` — the index's lock covers both, and
    the facade's own counters ride the same lock via the index.

    Captures are ASYNCHRONOUS: the engine loop thread only enqueues
    (``insert_async``); a daemon worker runs the jitted capture call
    (including its one-time compile), the device->host fetch, the host
    copies, and the locked trie insert — so an admission completion
    costs the active rows one enqueue, preserving the engine's
    one-chunk-per-boundary stall bound.  The queue is BOUNDED: under
    backlog new captures are dropped (the cache is best-effort;
    ``insert_dropped`` counts them) rather than pinning unbounded
    device memory.  ``flush()`` drains the queue for deterministic
    tests/benches.
    """

    def __init__(self, max_bytes: int = 1 << 30):
        import queue
        import threading

        from mlcomp_tpu.cache.prefix_index import PrefixIndex
        from mlcomp_tpu.utils.trace import null_tracer

        # the engine re-points this at its flight recorder so capture
        # spans land in the same trace (on the worker's own track)
        self.tracer = null_tracer()
        self.index = PrefixIndex(max_bytes)
        for key in ("used_hits", "used_hit_tokens", "insert_errors",
                    "insert_dropped"):
            self.index.counters[key] = 0
        self._keys_axes: Optional[List[Tuple[str, int]]] = (  # guarded_by: loop [writes]
            None
        )
        self._q: "queue.Queue" = queue.Queue(maxsize=8)
        self._warned = False  # guarded_by: worker [writes]
        self._closed = False
        self._worker = threading.Thread(
            target=self._drain, daemon=True, name="prefix-kv-capture"
        )
        self._worker.start()

    # engine admission path -------------------------------------------

    def bind_layout(self, cache) -> None:  # graftcheck: runs-on(loop)
        """Record the engine cache's leaf order/axes once (abstract
        pytree is fine); lookups before the first capture share it."""
        if self._keys_axes is None:
            self._keys_axes = [
                (k, ax) for k, ax, _ in kv_leaf_items(cache)
            ]

    def lookup(self, ids):
        """Pinned longest-prefix lease for ``ids`` (or None).  The
        fault point is the chaos surface tools/chaoscheck.py drives:
        an armed raise here must be CONTAINED by the engine to a
        cache-bypass (degraded mode), never a failed request."""
        from mlcomp_tpu.utils.faults import inject

        inject("cache.lookup")
        return self.index.lookup(ids)

    def assemble(self, lease, width: int, start_pad: int,
                 n_tokens: int) -> List[np.ndarray]:
        assert self._keys_axes is not None, "bind_layout before assemble"
        return assemble_prefix_rows(
            lease.segments, self._keys_axes, width, start_pad, n_tokens
        )

    def insert_async(self, capture_call, cache, ids, start_pad: int,
                     capture_lo: int) -> None:
        """Queue a finished prefill's capture for the worker:
        ``capture_call(cache)`` (the engine's jitted row slice) runs
        there, off the engine loop thread.  ``cache`` is an immutable
        device pytree — holding it keeps its buffers alive until the
        capture lands."""
        import queue

        if self._closed:
            return
        try:
            self._q.put_nowait(
                (capture_call, cache, list(ids), start_pad, capture_lo)
            )
        except queue.Full:
            with self.index._lock:
                self.index.counters["insert_dropped"] += 1

    def flush(self) -> None:
        """Block until every queued capture has been inserted (or
        failed) — determinism for tests and benches."""
        self._q.join()

    def close(self) -> None:
        """Drop queued captures (releasing their device cache
        references) and stop the worker.  Idempotent; the engine's
        close() calls it so repeated engine construct/close cycles
        don't accumulate orphan threads holding HBM."""
        import queue

        if self._closed:
            return
        self._closed = True
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
            self._q.task_done()
        self._q.put(None)  # wakes the worker; it exits on the sentinel

    def _drain(self) -> None:  # graftcheck: runs-on(worker)
        import warnings

        while True:
            item = self._q.get()
            if item is None:
                self._q.task_done()
                return
            capture_call, cache, ids, start_pad, lo = item
            try:
                # chaos surface: an armed raise lands in the except
                # below (insert_errors — serving continues uncached)
                from mlcomp_tpu.utils.faults import inject

                inject("cache.capture")
                # device->host fetch + host copies + trie insert, off
                # the engine loop thread — spanned so a slow capture
                # shows up on the worker's track, not as engine stall
                with self.tracer.span(
                    "prefix_cache.capture", tokens=len(ids),
                    capture_lo=lo,
                ) as sp:
                    rows = [np.asarray(r) for r in capture_call(cache)]
                    sp["new_tokens"] = self.insert(
                        ids, rows, start_pad, lo
                    )
            except Exception as e:  # best-effort: never kill serving
                with self.index._lock:
                    self.index.counters["insert_errors"] += 1
                if not self._warned:
                    self._warned = True
                    warnings.warn(
                        f"prefix-cache capture failed ({e!r}); serving "
                        "continues uncached for affected prompts"
                    )
            finally:
                self._q.task_done()

    def insert(self, ids, captured_rows, start_pad: int,
               capture_lo: int) -> int:
        """Store a finished prefill's captured rows (slot span
        [capture_lo, s_bucket)); dedup against the trie — only rows for
        tokens the trie doesn't already hold are kept.  On a cache-hit
        admission the capture starts at the hit boundary, so the rows
        BELOW it never even left the device; the trie must already hold
        those tokens (it leased them) and insert() starts at the
        offset."""
        assert self._keys_axes is not None, "bind_layout before insert"
        offset = max(0, capture_lo - start_pad)
        n = len(ids) - offset
        if n <= 0:
            return 0
        block = block_from_capture(
            captured_rows, self._keys_axes,
            start_pad + offset - capture_lo, n,
        )
        return self.index.insert(ids, block, offset=offset)

    def record_hit(self, used_tokens: int) -> None:
        """Count a USED hit (tokens whose prefill the engine actually
        skipped — chunk-aligned, so <= the lease's matched length)."""
        with self.index._lock:
            self.index.counters["used_hits"] += 1
            self.index.counters["used_hit_tokens"] += used_tokens

    def stats(self) -> Dict[str, Any]:
        out = self.index.stats()
        out["capture_queue_depth"] = self._q.qsize()
        return out
