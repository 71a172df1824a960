"""Continuous-batching decode engine: token-granularity serving.

Per-row KV windows, per-row sampling knobs and static bucketed shapes,
used at their natural granularity:

- a fixed pool of ``slots`` decode rows runs ONE compiled decode
  program; every inner step each live row samples, forwards, and its
  token streams out at the next host boundary;
- a new request PREFILLS in bounded CHUNKS (round 5) interleaved with
  decode dispatches, and its cache rows are INSERTED into a free slot
  at a step boundary — the stall any joiner imposes on active rows is
  one chunk, not a whole prompt-bucket prefill, and all-pad chunks of
  a short prompt in a big bucket are skipped outright (the cache
  cursor jumps over them), so admission work scales with the REAL
  prompt length;
- finished rows free their slot immediately — no drain barrier, and
  queue order is FIFO over free slots, so no request can be starved by
  a stream of another bucket's arrivals;
- per-row cache cursors (``cache_cursor``, models/transformer.py) let
  every row sit at a different depth in the shared cache buffers.

TPU-first consequences: shapes never change (slot count, buffer length,
prompt buckets and the prefill chunk are static), so the engine
compiles a handful of programs total; the decode program's carry
(cache, logits, presence) is donated, so the cache updates stay
in-place; sampling knobs ride as traced (slots,) arrays — any knob mix
shares the one decode program.

Host dispatch amortization (round 5, r4 verdict missing #1/#4): the
decode program runs ``steps_per_dispatch`` (K) single-token steps in
one ``lax.scan`` with per-row early-exit masking, so the host pays ONE
dispatch + ONE sync per K tokens instead of per token.  A row that
hits EOS or its budget mid-dispatch stops emitting on device (its
later inner steps are masked); joins still happen at dispatch
boundaries, so K bounds the extra join latency at K-1 steps.  K=1
recovers the round-4 per-token behavior exactly.
Since the adaptive-K PR the serve default is
``steps_per_dispatch="adaptive"``: a hysteretic ladder controller
(``dispatch_control.py``) re-picks K at every boundary from the live
queue-depth/occupancy signals — shallow queues small K (TTFT), deep
queues large K (amortization) — over a warmup-precompiled program
ladder; emitted tokens are bit-identical under ANY K schedule because
each request's sampling stream is keyed by (engine seed, request,
token position), never by dispatch grouping.

Async dispatch pipeline (the per-dispatch host cost next to the device
compute it can hide behind is not measured on this chip): the drive loop keeps up to
``pipeline_depth`` dispatches IN FLIGHT — dispatch N+1 is issued with
the donated decode carry before dispatch N's packed token buffer is
read back, so the host's dispatch+unpack work for N runs concurrent
with the device executing N+1 (JAX's async dispatch sequences the
donated carry chain on the device stream; the host never blocks to
issue).  Depth 1 is exactly the old synchronous loop (the debug/bisect
mode).  Nothing in the steady state drains the pipeline: an
admission's final INSERT chains on the donated carry like a dispatch
does (see the fused-admission paragraph below), and FINISH boundaries
need no drain either: the device retires rows itself, so an extra
in-flight dispatch on a finished row emits nothing — the host just
learns of the finish one boundary later.

Fused prefill+decode dispatch (the staged path's
``admission_stall_ms.chunked_max`` was barely better than the
monolithic prefill it replaced; neither is measured on this chip): the staged admission path ran every
prefill chunk as a LONE dispatch at a drained pipeline boundary, so
each chunk gapped the decode stream by a full host dispatch + the
chunk's compute.  Now an admission's chunk rides the SAME jitted
program as the boundary's K decode steps — one combined donated
dispatch (one per chunk width, ``_fused_dispatch_fn``)
runs the decode scan over all active slots AND one ``(1, c)`` chunk
against the admission's carried cache, sharing one weights argument so
parameters stream from HBM once per dispatch instead of twice.  The
pipeline does not drain for admissions: chunks compose on the
admission's own fresh cache, and the final insert-at-slot (and
prefix-cache capture) is ENQUEUED behind the last fused dispatch — its
operands are that dispatch's outputs, so the device runs fused
dispatch -> insert -> next dispatch back to back, and the host sets up
the next boundary (and the next admission) meanwhile.  The slot comes
from the host view, which only under-reports free slots; a row is
inactive in every dispatch issued before its insert, and
``_Slot.since_seq`` keeps tokens a cancelled predecessor still has in
flight from being booked to it.  What an admission costs the rows
decoding is the chunk's and the insert's device time, no host wait
(``admission_stall_ms`` is the host's enqueue time).  The import and
export completions of a disaggregated handoff still drain first.
Decode rows are bit-identical to the staged path by construction: the
fused trace embeds the SAME dispatch body (same scan order, same RNG
stream — chunks consume no RNG), and ``fused_admission=False`` forces
the staged path for bisection (``--engine-staged-admission``).

Mesh composition (round 5, r4 verdict missing #2; first-class since
the sharded-serving PR): pass ``mesh`` and the engine's
prefill/insert/decode programs run as SPMD programs over it — weights
arrive sharded (Megatron tp layout from the service loader), the
per-slot KV cache shards by XLA propagation from the tp-sharded K/V
projections, and the Pallas int8 paths (quant_kernel, kv_quant) run
inside shard_map islands (ops/quant.sharded_quant_matmul,
decode_attention.sharded_decode_attention — they read the process
mesh, which ``serve.load_service`` installs).  The host drives the
same numpy knob rows; under SPMD they replicate.  The sharded path is
now a PEER of the single-device one: the dispatch pipeline runs at
depth 2 by default under a mesh too (the donated carry chains on the
device stream with its shardings preserved — explicit carries pin
them with sharding constraints, so donation aliases buffers instead
of resharding), the paged KV layout serves sharded (page arrays
shard over tp at the kv-head axis, tables and the allocator's host
mirror replicate; the kv8 family routes through the lax sandwich
over the mesh-aware dense core until the paged kernels grow shard_map
islands — the named follow-up), and a multi-host gang serves through
``serve --distributed``: process 0 owns the HTTP front door and
submit queue and broadcasts per-boundary admission/retire/K decisions
over a TCP side channel (``parallel/distributed.BoundaryChannel``) so
every process executes the identical dispatch sequence.  The host
prefix cache remains single-chip (rejected with a message naming the
follow-up).

Resilience layer (this PR): failure behavior is defined, not
emergent.  Every request may carry a deadline and a cancel handle
(``submit(..., deadline_s=...)``, ``cancel(rid)``) — the loop retires
expired/cancelled requests at the next dispatch boundary (queued ones
fail in place, active rows are deactivated ON DEVICE and free their
slot), so a stuck client or an abandoned stream never holds a slot
past one boundary.  A raise inside the loop fails every in-flight and
queued future with the error and the thread dies CLEANLY; the
watchdog thread (``dispatch_stall_timeout``) detects both that death
and a dispatch wedged in the runtime (busy-clock timeout: waiters are
failed host-side with ``EngineStalled`` in bounded time), marks the
engine unhealthy (serve's /healthz 503), and performs one bounded,
progress-gated restart on a fresh device carry.  Prefix-cache faults
are contained to a cache-bypass (degraded mode), never a failed
request.  The fault points live in utils/faults.py;
tools/chaoscheck.py drives a live daemon through each and asserts
recovery invariants; the per-boundary maintenance cost is not
measured on the chip.

No upstream analog: the reference framework has no serving path at all.
"""

from __future__ import annotations

import itertools
import math
import os
import queue
import threading
import time
import warnings
from collections import deque
from concurrent.futures import Future
from contextlib import contextmanager
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import numpy as np

from mlcomp_tpu.models.counts import count_groups
from mlcomp_tpu.utils.faults import inject as _inject_fault
from mlcomp_tpu.utils.trace import (
    Tracer,
    make_trace_id,
    null_tracer,
    valid_trace_id,
)

_POISON = object()  # close() wakes a blocked queue.get with this


class DeadlineExceeded(RuntimeError):
    """The request's ``deadline_s`` passed before it finished; it was
    retired at the next dispatch boundary.  HTTP maps this to 504."""

    status = "deadline_exceeded"


class RequestCancelled(RuntimeError):
    """The request was cancelled (``cancel(rid)`` — e.g. the HTTP
    client disconnected) and retired at the next dispatch boundary."""

    status = "cancelled"


class EngineStalled(RuntimeError):
    """The watchdog declared a dispatch wedged (it exceeded
    ``dispatch_stall_timeout``) or found the drive loop dead; in-flight
    requests fail with this, distinguishable from a plain engine
    error."""

    status = "engine_stalled"


class NotCoordinator(RuntimeError):
    """This process is a FOLLOWER in a distributed serve gang: it
    executes the coordinator's broadcast dispatch sequence and owns no
    submit queue.  Send traffic to the coordinator (process 0) — its
    ``/healthz`` answers ``ready: true``; followers answer false so
    the fleet router never targets them.  HTTP maps this to 503."""

    status = "not_coordinator"


class ProfileBusy(RuntimeError):
    """A second ``profile()`` arrived while a device capture was
    already armed or mid-window — one capture at a time (the
    ``jax.profiler`` session is process-global).  HTTP maps this to
    409."""

    status = "profile_busy"


def _sown_by_group(counters) -> Dict[str, List[Any]]:
    """The leaves of a ``counters`` collection (or of its shapes), one a
    layer, by the group they are sown under: a float32 vector a call,
    whose entries the layer's file names in a table
    (models/counts.py).  A program hands back the groups its model's
    layers sow, joined in the tables' order, as the tail of its packed
    token buffer."""
    import jax

    from mlcomp_tpu.cache.kv_store import _leaf_name

    found: Dict[str, List[Any]] = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(counters):
        found.setdefault(_leaf_name(path), []).append(leaf)
    return {group.name: found[group.name] for group in count_groups(found)}


def _sown_counts(upd):
    """The ``counters`` collection of one model call, each group summed
    over its layers and the groups joined (``_sown_by_group``'s
    order), float32; None for a model that sows nothing."""
    import jax.numpy as jnp

    sums = [sum(leaves[1:], leaves[0])
            for leaves in _sown_by_group(upd.get("counters", {})).values()]
    if not sums:
        return None
    return sums[0] if len(sums) == 1 else jnp.concatenate(sums)


def _pack_counts(packed, counts):
    """The packed token buffer flattened, the counts as its tail: still
    one buffer, one transfer (``_process_oldest`` splits it)."""
    import jax.numpy as jnp

    return jnp.concatenate([packed.reshape(-1), counts.astype(packed.dtype)])


def bucket(value: int, buckets: Sequence[int], what: str) -> int:
    """The smallest configured bucket that holds ``value``."""
    for b in sorted(buckets):
        if value <= b:
            return b
    raise ValueError(
        f"{what} {value} exceeds the largest configured bucket "
        f"{max(buckets)}; raise the bucket list"
    )


def left_pad_row(ids: Sequence[int], s_bucket: int, pad_id: int):
    """The serving LEFT-padding contract, in one place: returns the
    (s_bucket,) int32 id row and its bool validity mask."""
    row = np.full(s_bucket, pad_id, np.int32)
    mask = np.zeros(s_bucket, bool)
    row[s_bucket - len(ids):] = ids
    mask[s_bucket - len(ids):] = True
    return row, mask


def _fail_future(fut: Future, err: Exception) -> None:
    """Fail a future idempotently: submit's close-race check and close's
    queue drain can both reach the same future — a bare done()-then-
    set_exception pair races to InvalidStateError."""
    try:
        if not fut.done():
            fut.set_exception(err)
    except Exception:  # InvalidStateError: the other side resolved it
        pass


def _set_result(fut: Future, result) -> None:
    """Resolve a future idempotently: the watchdog may have failed it
    already (stall declared, then the wedged dispatch returned and the
    loop finished the row normally) — the watchdog's verdict stands."""
    try:
        if not fut.done():
            fut.set_result(result)
    except Exception:  # InvalidStateError: lost the race
        pass


class _Slot:
    __slots__ = (
        "req", "cursor", "position", "start", "remaining", "emitted",
        "t_first", "span_end", "alloc_upto", "since_seq",
    )

    def __init__(self, req, cursor, position, start, remaining,
                 since_seq=0):
        self.req = req
        # the first dispatch whose tokens are this row's: the insert
        # is enqueued BEHIND whatever is in flight, and a dispatch
        # issued before it may still carry tokens of the slot's
        # previous holder (one retired by cancel/deadline after that
        # dispatch went out) — _process_oldest skips those
        self.since_seq = since_seq
        self.cursor = cursor          # next cache slot this row writes
        self.position = position      # next RoPE position (real tokens)
        self.start = start            # first valid cache slot (pads before)
        self.remaining = remaining    # tokens still allowed
        self.emitted: List[int] = []
        self.t_first = None           # host time the first token landed
        # paged-layout lazy decode allocation (set at insert): the
        # row's write span end, and the slot-coordinate frontier its
        # allocated pages cover — _lazy_extend_tick grows the mapping
        # as the cursor approaches the frontier
        self.span_end = None
        self.alloc_upto = None


class _Admission:
    """A prefill in progress: one chunk runs per loop boundary, decode
    dispatches run between chunks (r4 verdict missing #4)."""

    __slots__ = ("req", "s_bucket", "chunk", "n_chunks", "next_chunk",
                 "row", "positions", "kv_mask", "cache", "last_logits",
                 "capture_lo", "skip_capture", "chunks_run", "fused_chunks",
                 "stall_ms", "page_lease", "handoff", "t_admit", "t_booked",
                 "boundary0")

    def __init__(self, req, s_bucket, chunk, first_chunk):
        self.req = req
        self.s_bucket = s_bucket
        self.chunk = chunk
        self.n_chunks = s_bucket // chunk
        self.next_chunk = first_chunk   # all-pad chunks before are skipped
        self.row = None                 # (1, s_bucket) ids, set by starter
        self.positions = None           # (1, s_bucket) host; sliced per chunk
        self.kv_mask = None             # (1, l_buf) DEVICE; uploaded once
        self.cache = None               # carried across chunks
        self.last_logits = None
        self.capture_lo = 0             # first RUN chunk boundary (slots):
        # rows below it came from the prefix cache (or are pads) and
        # are never captured back
        self.skip_capture = False       # trie already holds the FULL
        # prompt (retry storm): re-capturing would fetch rows only to
        # dedup to zero new tokens
        self.chunks_run = 0             # prefill chunks this admission ran
        self.fused_chunks = 0           # ... of which rode a decode dispatch
        # host-observed decode-stream stall this admission imposed
        # (staged chunks + the insert boundary, counted only while
        # decode rows were active) — the admission_stall_ms histogram
        self.stall_ms = 0.0
        self.page_lease = None          # device prefix-registry hit
        # (kvpool.PageLease): pages retained until the insert commits
        # the table row (shared COW mapping) or the admission dies
        self.handoff = None             # IMPORT admission (decode side
        # of a disaggregated handoff): the parsed payload — no chunks
        # run; the completion boundary writes pages + inserts the slot
        # the lane's books (_take_lane sets them): the stamp of the
        # ``admit`` instant, the stamp lane_busy_ms is booked up to, and
        # the loop iteration the admission began in
        self.t_admit = self.t_booked = 0.0
        self.boundary0 = 0


class DecodeEngine:
    """Fixed-slot continuous batcher around a decode-capable model.

    ``submit`` returns a Future resolving to the full result dict; pass
    ``stream`` (a ``queue.Queue``) to additionally receive per-token
    dicts ``{"token", "logprob", "step"}`` as they land (in bursts of
    up to ``steps_per_dispatch``), terminated by ``None``.  Greedy
    outputs are identical to ``generate`` on the same weights: the
    prefill and per-step math run the same model code, and each row's
    logits never depend on its neighbours.
    """

    def __init__(
        self,
        model,
        variables,
        slots: int = 8,
        prompt_buckets: Sequence[int] = (128, 256, 512, 1024),
        max_new_cap: int = 128,
        pad_id: int = 0,
        quant_kernel: bool = False,
        seed: int = 0,
        steps_per_dispatch: "Optional[int | str]" = None,
        prefill_chunk: int = 256,
        mesh=None,
        prefix_cache=None,
        pipeline_depth: Optional[int] = None,
        flight_recorder_events: Optional[int] = 32768,
        metrics=None,
        dispatch_stall_timeout: Optional[float] = None,
        fused_admission: Optional[bool] = None,
        kv_layout: str = "dense",
        kv_page_tokens: Optional[int] = None,
        kv_pages: Optional[int] = None,
        max_slots: Optional[int] = None,
        k_ladder: Optional[Sequence[int]] = None,
        dist=None,
        prefill_only: bool = False,
    ):
        import jax
        import jax.numpy as jnp

        self.model = model
        # PREFILL-ONLY mode (disaggregated serving's prefill half): the
        # engine runs ONLY the admission core — chunked prefill, prefix
        # cache, capture — and a completed admission EXPORTS the
        # prompt's KV as page-tile handoff payloads instead of
        # inserting into a decode slot.  No decode dispatches ever
        # issue, so the slot carry is forced to one throwaway row and
        # the fused/pipelined decode machinery stays inert (there is no
        # decode dispatch for a chunk to ride).  This is the pure
        # batched-forward shape the BERT/scoring fast path shares.
        self.prefill_only = bool(prefill_only)
        if self.prefill_only:
            if dist is not None:
                raise ValueError(
                    "prefill_only does not compose with distributed "
                    "serving (the gang synchronizes DECODE boundaries); "
                    "run prefill replicas single-process"
                )
            if mesh is not None:
                raise ValueError(
                    "prefill_only is single-chip for now (the export "
                    "capture fetches host rows, which does not compose "
                    "with a sharded admission cache — the sharded "
                    "prefill tier is a named follow-up); drop the mesh"
                )
            if kv_layout != "dense":
                raise ValueError(
                    "prefill_only engines keep the dense admission "
                    "cache (there are no decode slots to page); pass "
                    "kv_page_tokens to pick the EXPORT page size"
                )
            if kv_pages is not None or max_slots is not None:
                raise ValueError(
                    "kv_pages / max_slots need a decode slot pool; a "
                    "prefill_only engine has none"
                )
            # one throwaway carry row: the decode state is never
            # dispatched, so slots would only burn HBM
            slots = 1
            pipeline_depth = 1
            fused_admission = False
        self.slots = int(slots)
        self.prompt_buckets = tuple(sorted(prompt_buckets))
        self.max_new_cap = int(max_new_cap)
        self.pad_id = int(pad_id)
        self.quant_kernel = bool(quant_kernel)
        # steps_per_dispatch: an int PINS K (the bisect mode, and what
        # the benchmark's configurations set); "adaptive" runs the
        # load-to-K ladder controller
        # (dispatch_control.AdaptiveKController) — shallow
        # queues pick small K (TTFT), deep queues large K (dispatch
        # amortization), hysteresis keeps the precompiled ladder warm.
        # Tokens are bit-identical under ANY K schedule by
        # construction (each request's sampling keys derive from
        # (engine rng, request seed, token position) — see
        # _fresh_dstate's rseed; a GLOBAL step counter would NOT be
        # K-invariant, because a row's activation boundary depends on
        # K under mid-stream admission — and the scan body at K is
        # the K=1 body iterated), so adaptivity moves time, never
        # tokens.
        # None resolves to 4.
        from mlcomp_tpu.dispatch_control import (
            DEFAULT_LADDER,
            AdaptiveKController,
        )

        adaptive = (
            isinstance(steps_per_dispatch, str)
            and steps_per_dispatch.strip().lower() == "adaptive"
        )
        if isinstance(steps_per_dispatch, str) and not adaptive:
            raise ValueError(
                "steps_per_dispatch must be an int, None, or "
                f"'adaptive'; got {steps_per_dispatch!r}"
            )
        self._k_controller = None
        if adaptive:
            ladder = tuple(
                int(k) for k in (k_ladder or DEFAULT_LADDER)
            )
            self._k_controller = AdaptiveKController(ladder)
            self.k_ladder = self._k_controller.ladder
            steps_per_dispatch = self.k_ladder[0]
        elif k_ladder is not None:
            raise ValueError(
                "k_ladder only applies to steps_per_dispatch="
                "'adaptive' (got a pinned/default steps_per_dispatch)"
            )
        if steps_per_dispatch is None:
            steps_per_dispatch = 4
        self.steps_per_dispatch = int(steps_per_dispatch)
        if not adaptive:
            self.k_ladder = (self.steps_per_dispatch,)
        self.adaptive_k = adaptive
        if self.steps_per_dispatch < 1:
            raise ValueError("steps_per_dispatch must be >= 1")
        self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        # fused admission (default ON): a pending admission's prefill
        # chunk rides the decode dispatch as one combined program, so
        # decode never pauses for a prefill.  False forces the staged
        # path — every chunk its own dispatch at a drained boundary —
        # kept as the bisect/debug mode (--engine-staged-admission);
        # outputs are bit-identical either way (the fused program
        # embeds the same dispatch body).
        self.fused_admission = (
            True if fused_admission is None else bool(fused_admission)
        )
        self.mesh = mesh
        # multi-host serve gang (parallel/distributed.BoundaryChannel):
        # process 0 (the coordinator) owns the submit queue and
        # broadcasts per-boundary admission/retire/K decisions; every
        # other process replays them, so the whole gang executes the
        # IDENTICAL dispatch sequence over the global mesh.  The
        # broadcast is plain TCP (no device collectives), so it never
        # interleaves with the SPMD programs it sequences.
        self._dist = dist
        if dist is not None and mesh is None:
            raise ValueError(
                "distributed serving (dist=...) needs a mesh: the gang "
                "runs one SPMD program over the global device mesh"
            )
        # in-flight dispatch pipeline depth D: the loop issues dispatch
        # N+1 with the donated carry BEFORE blocking on dispatch N's
        # packed outputs, hiding the host's dispatch+unpack cost behind
        # device compute.  None resolves to 2 (double buffering) — mesh
        # or not: under SPMD the donated carry chains on the device
        # stream exactly like single-chip (the per-dispatch host
        # cost the pipeline hides is, if anything, LARGER multi-chip),
        # and the carry keeps its shardings through the chain (the
        # dispatch programs pin them with sharding constraints where
        # they are explicit).  Depth 1 stays the debug/bisect mode.
        if pipeline_depth is None:
            pipeline_depth = 2
        self.pipeline_depth = int(pipeline_depth)
        if self.pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {pipeline_depth}"
            )
        # host-RAM prefix KV cache (mlcomp_tpu/cache): lookup on
        # admission, capture on prefill completion.  Host->device row
        # inserts would fight XLA's cache sharding under SPMD, so the
        # cache is single-chip.
        self.prefix_cache = prefix_cache
        if prefix_cache is not None and mesh is not None:
            raise ValueError(
                "the prefix KV cache is single-chip for now (host-side "
                "row inserts don't compose with a sharded cache; "
                "sharding the capture/assemble tier is the "
                "sharded-serving PR's named follow-up — the device "
                "prefix-page REGISTRY already serves sharded paged "
                "engines); drop prefix_cache or the mesh"
            )
        if prefix_cache is not None:
            # hits are chunk-granular: a bucket that prefills as ONE
            # chunk (smaller than prefill_chunk, or not divisible by
            # it) can never hit — captures at it only feed OTHER
            # buckets.  Silent zero-hit configs are this PR's cliff
            # class; say so at construction.
            mono = [
                s for s in self.prompt_buckets
                if s <= self.prefill_chunk or s % min(
                    self.prefill_chunk, s
                )
            ]
            if mono:
                warnings.warn(
                    f"prefix-cache hits are impossible at prompt "
                    f"bucket(s) {mono}: each prefills as a single "
                    f"chunk (prefill_chunk={self.prefill_chunk}), and "
                    "hits skip whole chunks only — shrink "
                    "prefill_chunk to a divisor of every bucket to "
                    "cache-serve them",
                    stacklevel=2,
                )
        # +1 scratch slot: a RETIRED row's frozen cursor still receives
        # the dispatch's cache write (the device retires rows by
        # masking emission, not by skipping the forward), and its write
        # span ends one past the last budgeted slot.  The per-row DUS
        # writes CLAMP at the buffer edge (scatter used to drop), so
        # without the scratch slot a dead row would overwrite its own
        # last real K/V — harmless today (retired rows are never read
        # before slot reuse) but a corruption trap for any future
        # reader.  (The int8 cache's single-token step no longer writes
        # a row without a request: decode_attention appends for the
        # rows it walks.  The bf16 cache still does, so the slot
        # stays.)
        self.l_buf = self.prompt_buckets[-1] + self.max_new_cap + 1
        self.vocab = int(getattr(model, "vocab_size"))
        self._jax, self._jnp = jax, jnp
        # what one row of the model's cache holds, and what its layers
        # count: shapes only.  The carry stays a pytree the engine does
        # not look into; it reads the leaves' NAMES here, once, to
        # refuse what a cache of this kind cannot do and to size the
        # counters' channel.
        from mlcomp_tpu.cache.kv_store import SLOT_AXES, _leaf_name
        from mlcomp_tpu.models.generation import decode_shapes

        shapes = decode_shapes(self.model, 1, self.l_buf)
        cache_abs = shapes["cache"]
        cache_leaves = {
            _leaf_name(path): leaf for path, leaf
            in jax.tree_util.tree_leaves_with_path(cache_abs)
        }
        # a leaf with no slot axis is per-slot STATE of fixed size (a
        # recurrent layer's), not keys and values a token: it cannot be
        # cut into pages or token spans, so whatever moves KV by pages
        # or by prefix is refused here, by the leaf
        state_leaves = sorted(
            name for name, leaf in cache_leaves.items()
            if leaf.ndim and name not in SLOT_AXES
        )
        if state_leaves:
            asked = [what for what, on in (
                ("kv_layout='paged' (and with it the import of a KV "
                 "handoff)", kv_layout == "paged"),
                ("prefix_cache", prefix_cache is not None),
                ("prefill_only (KV export)", self.prefill_only),
            ) if on]
            if asked:
                raise ValueError(
                    f"{', '.join(asked)}: the model's cache holds per-"
                    f"slot state {state_leaves} with no token axis, "
                    "which has no pages and no prefix to share or hand "
                    "off; serve it with the dense layout and no prefix "
                    "cache"
                )
        sown = _sown_by_group(shapes.get("counters", {}))
        self._count_groups = count_groups(sown)
        self._count_layers = {
            group: len(leaves) for group, leaves in sown.items()
        }
        self._count_entries: Tuple[Tuple[str, str, str], ...] = tuple(
            (group.name, name, what)
            for group in self._count_groups
            for name, what in group.entries
        )

        # paged device KV (mlcomp_tpu/kvpool, kv_layout="paged"): the
        # cache buffer becomes (num_pages, page_tokens, ...) blocks
        # gathered through per-slot page tables, so sequence length is
        # paid per page, admission is gated by FREE PAGES instead of a
        # worst-case slot reservation, the live slot count is ELASTIC
        # up to max_slots, and prefix-sharing maps pages copy-on-write.
        # Dense stays the default and the bisect mode — the paged
        # dispatch wraps the UNCHANGED dispatch core between a page
        # gather and scatter, so outputs are bit-identical by
        # construction (and by test).
        self.kv_layout = str(kv_layout)
        if self.kv_layout not in ("dense", "paged"):
            raise ValueError(
                f"kv_layout must be 'dense' or 'paged', got {kv_layout!r}"
            )
        self._pool = None
        self._layout = None
        self._slots_floor = self.slots
        self.max_slots = self.slots
        if self.kv_layout == "dense":
            if max_slots is not None and int(max_slots) != self.slots:
                raise ValueError(
                    "elastic slots (max_slots) need kv_layout='paged'; "
                    "the dense layout reserves worst-case KV per slot "
                    "at construction"
                )
            if (kv_page_tokens is not None and not self.prefill_only) \
                    or kv_pages is not None:
                raise ValueError(
                    "kv_page_tokens / kv_pages only apply to "
                    "kv_layout='paged' (kv_page_tokens additionally "
                    "picks a prefill_only engine's EXPORT page size)"
                )
        else:
            from mlcomp_tpu.kvpool import (
                RESERVED_PAGES,
                PagedLayout,
                PagePool,
            )
            # one chunk width per bucket (the admission geometry):
            # pages must tile every chunk so registry-hit boundaries
            # (chunk-quantized, like the host prefix cache's) land on
            # page boundaries — the quantum the page size aligns to
            T = self._page_quantum(
                kv_page_tokens,
                "chunk-aligned prefix boundaries must land on page "
                "boundaries",
            )
            # num_pages unset: the default pool budget below is itself
            # derived from the layout's max_pages
            layout = PagedLayout(cache_abs, self.l_buf, T)
            if kv_pages is None:
                # default budget = the DENSE layout's KV bytes: `slots`
                # worst-case rows' worth of pages — equal HBM, but paid
                # per page, so mixed-length traffic fits far more
                # streams before admission rejects
                kv_pages = RESERVED_PAGES + self.slots * layout.max_pages
            layout.num_pages = int(kv_pages)
            if layout.num_pages - RESERVED_PAGES < layout.max_pages:
                raise ValueError(
                    f"kv_pages={kv_pages} cannot hold even one "
                    f"worst-case request ({layout.max_pages} pages of "
                    f"{T} tokens + {RESERVED_PAGES} reserved)"
                )
            if max_slots is None:
                max_slots = 4 * self.slots
            self.max_slots = int(max_slots)
            if self.max_slots < self.slots:
                raise ValueError(
                    f"max_slots={max_slots} below slots={self.slots}"
                )
            self._layout = layout
            self._pool = PagePool(layout, max_slots=self.max_slots)
            # attention data path (MLCOMP_TPU_PAGED_ATTN): how the
            # decode dispatch reads/writes KV through the pages.
            #   auto   (default) — FUSED: the dispatch core's attention
            #          reads K/V through the page table directly (paged
            #          Pallas kernels where the geometry keeps the
            #          dense block partition, per-layer lax gathers
            #          elsewhere) and appends the new token's K/V into
            #          its page in place — no dense view materializes;
            #   pallas — fused, and the paged kernels are REQUIRED
            #          (ineligible geometry raises — the loud bisect);
            #   lax    — the PR-7 reference sandwich: gather the dense
            #          view, run the unchanged core, scatter back.
            #          Kept everywhere as the correctness reference.
            # All three are bit-identical to dense by construction and
            # by test (tests/test_engine_paged.py).
            self._paged_attn = os.environ.get(
                "MLCOMP_TPU_PAGED_ATTN", "auto"
            )
            if self._paged_attn not in ("auto", "pallas", "lax"):
                raise ValueError(
                    "MLCOMP_TPU_PAGED_ATTN must be auto/pallas/lax, got "
                    f"{self._paged_attn!r}"
                )
            if mesh is not None and self._paged_attn == "pallas":
                raise ValueError(
                    "MLCOMP_TPU_PAGED_ATTN=pallas does not compose "
                    "with a mesh yet (the paged attention kernels have "
                    "no shard_map islands — the sharded-serving PR's "
                    "named follow-up); use auto (the sharded fused/"
                    "sandwich routes) or lax (the reference sandwich)"
                )
            # gather IMPLEMENTATION (the lax sandwich's dense-view
            # gather, the registry's row-span fetches, and the fused
            # path's per-layer fallback gathers — the non-quant family
            # and kernel-ineligible geometries): "auto" picks the
            # Pallas scalar-prefetch DMA kernel on TPU and the
            # jnp.take lax reference elsewhere; the env override is
            # the bisect knob (lax on TPU isolates a kernel suspicion
            # in one restart).
            self._page_gather_impl = os.environ.get(
                "MLCOMP_TPU_PAGE_GATHER", "auto"
            )
            if mesh is not None:
                # the Pallas scalar-prefetch gather is a bare
                # pallas_call (no shard_map island yet — the same named
                # follow-up as the paged kernels): under a mesh "auto"
                # resolves to the jnp.take gather, which XLA partitions
                # with the rest of the SPMD program; forcing pallas is
                # rejected loudly rather than mis-partitioned silently
                if self._page_gather_impl == "pallas":
                    raise ValueError(
                        "MLCOMP_TPU_PAGE_GATHER=pallas does not compose "
                        "with a mesh (no shard_map island yet — the "
                        "sharded-serving PR's named follow-up); use "
                        "auto or lax"
                    )
                self._page_gather_impl = "lax"
            # does the fused data path run the paged ATTENTION KERNELS
            # (kv8 family whose buffer keeps the dense block partition
            # in whole pages), or per-layer gather fallbacks?  Decides
            # the bytes-moved cost model below.
            from mlcomp_tpu.ops.pallas.decode_attention import (
                paged_block_kv,
            )

            quant_specs = [
                s for s in layout.kv_specs
                if s.keystr.endswith("cached_key_q")
            ]
            self._kv_fused_kernels = bool(quant_specs) and all(
                paged_block_kv(
                    s.seq_len, s.shape[1], s.shape[3], T
                ) is not None
                for s in quant_specs
            )
            if mesh is not None and quant_specs:
                # SHARDED paged serving, kv8 family: the fused path's
                # attention is the paged Pallas kernels (or bare dense
                # kernels on gathered bytes) — neither has a shard_map
                # island yet, so "auto" resolves to the LAX SANDWICH:
                # gather the dense view through the (replicated) table,
                # run the UNCHANGED dense core — whose int8 attention
                # already runs sharded_decode_attention islands under
                # the mesh — and scatter back.  Bit-identical to dense
                # by the same construction as single-chip; the fused
                # sharded kernels are the named follow-up.  The f32
                # family keeps the fused path (append_rows scatter +
                # per-layer take gathers are plain XLA ops the SPMD
                # partitioner handles).
                self._paged_attn = "lax"
                self._kv_fused_kernels = False
            elif (quant_specs and not self._kv_fused_kernels
                  and self._paged_attn == "auto"):
                from mlcomp_tpu.ops.pallas import on_tpu

                if on_tpu():
                    # on the chip "auto" means the paged kernels: a
                    # page size they cannot serve is a construction
                    # error naming the geometry, never a quiet switch
                    # to the per-layer lax gather (CPU runs keep the
                    # gather route — it is their reference)
                    s = quant_specs[0]
                    raise ValueError(
                        f"kv_layout='paged': {T}-token pages cannot "
                        f"keep the decode kernel's block partition "
                        f"over the {s.seq_len}-slot int8 KV buffer "
                        f"(Hkv={s.shape[1]}, dh={s.shape[3]}); pick a "
                        "--kv-page-tokens that divides the kernel "
                        "block, or set MLCOMP_TPU_PAGED_ATTN=lax for "
                        "the reference gather"
                    )

        # EXPORT geometry (prefill_only): the page size the handoff
        # payloads tile to.  Same quantum rule as the paged layout —
        # pages must tile every prefill chunk so bucket boundaries are
        # page boundaries (every bucket is a whole number of chunks,
        # so s_bucket lands page-aligned and the prompt span exports
        # as whole tiles) — and the leaf inventory is the admission
        # cache's, recorded once so every export shares it.
        self._export_T: Optional[int] = None
        self._export_leaves = None
        if self.prefill_only:
            from mlcomp_tpu.cache.kv_store import kv_leaf_items

            self._export_T = self._page_quantum(
                kv_page_tokens,
                "handoff pages must tile the admission geometry",
            )
            self._export_leaves = [
                (keystr, axis, tuple(leaf.shape), leaf.dtype)
                for keystr, axis, leaf in kv_leaf_items(cache_abs)
            ]

        # weight prep mirrors generate(): entry-dequant everything the
        # kernel won't consume, fold the rest — ONCE, outside any step
        from mlcomp_tpu.ops.quant import (
            dequantize_nonkernel_params,
            dequantize_params,
            fold_kernel_leaves,
            has_quantized,
        )

        if has_quantized(variables):
            if self.quant_kernel:
                variables = fold_kernel_leaves(
                    dequantize_nonkernel_params(variables, jnp.bfloat16)
                )
            else:
                variables = dequantize_params(variables, jnp.bfloat16)
        self.variables = jax.tree.map(jnp.asarray, variables)

        self._seed = int(seed)
        # the jitted-program pool — built before the first carry (the
        # sharded fresh-dstate initializer is itself a pooled program)
        self._fns: Dict[Any, Any] = {}
        # multi-process gang: host->device uploads must be REPLICATED
        # global arrays (every process holds identical bytes — the
        # boundary broadcast guarantees it), and the packed dispatch
        # output must come back replicated so np.asarray can read it
        # on every host
        self._multiproc = (
            dist is not None and dist.num_processes > 1
        )
        # explicit carry shardings (donation must PRESERVE shardings —
        # the dispatch chain re-pins them with sharding constraints):
        # the NEW sharded paths get them explicitly — paged page
        # arrays shard over tp at the kv-head axis, tables/bookkeeping
        # replicate — while the certified single-process dense-mesh
        # path keeps XLA propagation (same programs as the MULTICHIP
        # dryruns).  Multi-process engines need them for BOTH layouts:
        # the fresh carry must be born as global arrays.
        self._carry_shardings = None
        if mesh is not None and (
            self._layout is not None or dist is not None
        ):
            self._carry_shardings = self._build_carry_shardings()
        self._dstate = self._fresh_dstate()  # guarded_by: loop
        self._host: List[Optional[_Slot]] = (  # guarded_by: loop [writes]
            [None] * self.slots
        )
        self._adm: Optional[_Admission] = None  # guarded_by: loop [writes]
        self._broken: Optional[Exception] = None
        self._abandoned = False
        self._queue: "queue.Queue" = queue.Queue()
        # loop-owned admission order: submit() enqueues into _queue (the
        # thread-safe handoff); the loop pumps it into _pending, where
        # deadline/cancel sweeps can retire QUEUED requests at a
        # dispatch boundary instead of only when a slot frees up
        self._pending: Deque[Dict[str, Any]] = deque()  # guarded_by: loop [writes]
        # rids cancelled via cancel() but not yet retired by the loop's
        # boundary sweep (set add/discard are atomic under the GIL; the
        # sweep runs on the loop thread)
        self._cancelled: set = set()
        self._stats = {  # guarded_by: loop [writes]
            "requests": 0, "steps": 0, "prefills": 0, "dispatches": 0,
            "prefill_chunks": 0, "emitted_tokens": 0,
            # fused-admission accounting: fused_chunks counts the
            # prefill chunks that rode a decode dispatch (every chunk
            # increments prefill_chunks exactly once, fused or staged
            # — no double count); admissions_overlapped the completed
            # admissions with at least one fused chunk
            "fused_chunks": 0, "admissions_overlapped": 0,
            "deadline_exceeded": 0, "cancelled": 0, "cache_degraded": 0,
            "watchdog_stalls": 0, "watchdog_restarts": 0,
            "profile_captures": 0,
            # adaptive-K: controller switches of steps_per_dispatch
            # (0 forever on pinned-K engines)
            "dispatch_k_changes": 0,
        }
        if self._pool is not None:
            # elastic-slot + device-registry accounting (paged only),
            # plus the lazy decode-page allocator's ledger: pages
            # allocated as cursors crossed page boundaries mid-stream
            # (instead of worst-case at insert), and the requests that
            # hit a dry pool at such a crossing (bounded failure)
            self._stats["slots_scaled"] = 0
            self._stats["kv_registry_hit_tokens"] = 0
            self._stats["kv_pages_lazy_allocated"] = 0
            self._stats["kv_decode_page_failures"] = 0
            # disaggregation, decode side: handoffs imported via
            # import_pages (pages written straight into the pool, no
            # prefill), bytes received, and typed rejects (truncated/
            # mismatched blobs — a prefill replica dying mid-transfer)
            self._stats["handoffs_imported"] = 0
            self._stats["kv_pages_imported"] = 0
            self._stats["handoff_bytes_imported"] = 0
            self._stats["handoff_rejects"] = 0
        if self.prefill_only:
            # disaggregation, prefill side: completed admissions
            # exported as page-payload handoffs
            self._stats["handoffs_exported"] = 0
            self._stats["kv_pages_exported"] = 0
            self._stats["handoff_bytes_exported"] = 0
        self._fatblock_scale_warned = False
        # issued-but-unprocessed dispatches, oldest first: (packed
        # device buffer, host issue time, dispatch seq — the flight
        # recorder's async-span id — and the step depth it was issued
        # at, for the lazy page allocator's mixed-K lookahead).  Owned
        # by the loop thread; close()'s normal path touches it only
        # after the join.
        self._inflight: Deque[Tuple[Any, float, int, int]] = deque()  # guarded_by: loop [writes]
        # the loop thread's time, booked by _account at the stamps its
        # spans share: host_ms is every stretch outside resolve's
        # blocked fetch (wait_ms) and outside the idle poll
        # (idle_wait, booked nowhere); hidden_ms is the part of
        # host_ms spent while at least one dispatch was in flight;
        # inflight_sum/issued is the mean in-flight depth at issue
        # (occupancy); rows_attended/rows_total is the share of the
        # carry's rows that held a request when a dispatch went out;
        # kv_rows_written is those rows times the dispatch's steps;
        # inserts_behind_dispatch is the admissions whose insert was
        # enqueued with a dispatch still unresolved (over prefills:
        # how often a completion found the pipeline running).
        # The admission lane's books: rows_starved is the rows of
        # rows_total that stood empty at issue while a request waited
        # for one (queued, or mid-prefill in the lane); lane_busy_ms is
        # the admit -> inserted stretches, from the stamps of the
        # ``admission`` span; blocked_{slot,lane,pages}_ms are the
        # boundaries' lengths by why that boundary's admission tick
        # left the queue's head waiting (_book_boundary); lane_boundaries
        # over lane_admissions is the loop iterations an admission held
        # the lane
        self._pstats = {  # guarded_by: loop [writes]
            "issued": 0, "host_ms": 0.0, "hidden_ms": 0.0, "wait_ms": 0.0,
            "inflight_sum": 0, "peak_inflight": 0,
            "inserts_behind_dispatch": 0,
            "rows_attended": 0, "rows_total": 0, "kv_rows_written": 0,
            "kv_attended": 0, "kv_live": 0,
            "kv_attended_window": 0, "kv_live_window": 0, "kv_fetched": 0,
            "kv_trips": 0,
            "rows_starved": 0, "lane_busy_ms": 0.0,
            "blocked_slot_ms": 0.0, "blocked_lane_ms": 0.0,
            "blocked_pages_ms": 0.0,
            "lane_boundaries": 0, "lane_admissions": 0,
        }
        # loop iterations closed, the stamp the blocked_*_ms books are
        # closed up to (a boundary's opening, or the end of its
        # idle_wait), and the _pstats key this boundary's admission tick
        # named for the queue's head (None: nobody waits, or the head
        # goes in at the next tick)
        self._boundary_n = 0  # guarded_by: loop [writes]
        self._t_lane = time.perf_counter()  # guarded_by: loop [writes]
        self._head_blocked: Optional[str] = None  # guarded_by: loop [writes]
        # the admission whose ``admission`` span is open (_take_lane ->
        # _leave_lane): ``_adm``, but for the stretch of
        # _start_admission in which ``_adm`` is not yet set
        self._in_lane: Optional[_Admission] = None  # guarded_by: loop [writes]
        # one entry an attention layer: its window, None where it
        # reads the whole context (a model that does not say is one
        # layer of full attention: the share then reads 1)
        windows = getattr(model, "attention_windows", None)
        self._attn_windows: Tuple[Optional[int], ...] = (
            tuple(windows()) if callable(windows) else (None,)
        )
        # (buffer length, granule) of the dense int8 cache that the
        # single-token decode_attention walks: what kv_tokens_fetched
        # and kv_trips are counted from.  None where no such walk runs
        # (a bfloat16 cache; the paged layout, whose kernels move whole
        # pages)
        self._kv_walk: Optional[Tuple[int, int]] = None
        self._attn_window_layers = {   # window -> layers that have it
            w: self._attn_windows.count(w) for w in set(self._attn_windows)
        }
        if self.kv_layout == "dense" and "cached_key_q" in cache_leaves:
            from mlcomp_tpu.ops.pallas.decode_attention import auto_block_kv

            shape = cache_leaves["cached_key_q"].shape
            # under a mesh each device walks its own KV heads
            tp = mesh.shape.get("tp", 1) if mesh is not None else 1
            self._kv_walk = (shape[2], auto_block_kv(
                shape[2], max(1, shape[1] // tp), shape[3]))
        # what the model's layers sowed, summed over every program
        # read back (_sown_counts' order); a staged chunk's counts wait
        # here for the next read
        self._counts = np.zeros((len(self._count_entries),), np.float64)  # guarded_by: loop [writes]
        self._counts_pending: Deque[Any] = deque()  # guarded_by: loop [writes]
        self._t_acct = time.perf_counter()  # guarded_by: loop [writes]
        # per-request latency reservoirs (most recent ~2k requests;
        # warmup submissions excluded): time-to-first-token and the
        # per-token decode interval behind the stats() percentiles.
        # The deques WINDOW the percentiles; the *_n lifetime counts
        # keep long runs honest — len(deque) saturates at maxlen and
        # silently misrepresents how many requests the percentiles
        # summarize
        self._lat_ttft: Deque[float] = deque(maxlen=2048)  # guarded_by: loop [writes]
        self._lat_tok: Deque[float] = deque(maxlen=2048)  # guarded_by: loop [writes]
        self._lat_ttft_n = 0  # guarded_by: loop [writes]
        self._lat_tok_n = 0  # guarded_by: loop [writes]
        # flight recorder: an always-on bounded ring of dispatch /
        # admission / prefix-cache / request-lifecycle events, exported
        # on demand (serve's GET /trace).  0/None disables; overhead is
        # a dict append per event: on against off, chat-steady's
        # tpot_p90_ms and ttft_p90_ms read the same within their
        # run-to-run spread on a v5e (PERF.md section 6, PR 39)
        self.recorder: Tracer = (
            Tracer(max_events=int(flight_recorder_events))
            if flight_recorder_events else null_tracer()
        )
        # which boundary compiled: the process's one compile listener
        # puts a ``compile`` instant on this recorder's engine.compile
        # track as each backend compile ends, on the thread that paid
        # for it, so it lies inside the loop span that did
        from mlcomp_tpu.utils import compiles

        self._compiles = compiles
        compiles.watch(self.recorder)
        self._rid = itertools.count(1)       # request-lifecycle trace ids
        self._dispatch_seq = itertools.count(1)
        if prefix_cache is not None:
            # the capture worker's spans land on its own thread track
            prefix_cache.tracer = self.recorder
        # metrics registry (mlcomp_tpu/obs): the caller (the serving
        # service) passes its scrape registry; standalone engines keep
        # a private one so instruments never need None-guards
        from mlcomp_tpu.obs.metrics import DEFAULT_MS_BUCKETS, Registry

        self.metrics = metrics if metrics is not None else Registry()
        self._hist_ttft = self.metrics.histogram(
            "mlcomp_engine_ttft_ms",
            "Submit -> first token at the host, per finished request",
            buckets=DEFAULT_MS_BUCKETS,
        )
        self._hist_tok = self.metrics.histogram(
            "mlcomp_engine_per_token_ms",
            "Mean decode interval after the first token, per request",
            buckets=DEFAULT_MS_BUCKETS,
        )
        self._hist_stall = self.metrics.histogram(
            "mlcomp_engine_admission_stall_ms",
            "Host-observed decode-stream stall per completed admission "
            "(staged chunks run while rows decode + the insert "
            "boundary; ~0 when every chunk rides a fused dispatch)",
            buckets=DEFAULT_MS_BUCKETS,
        )
        self._hist_device = self.metrics.histogram(
            "mlcomp_engine_device_time_ms",
            "Device-lane busy ms per dispatch (one observation per "
            "/profile capture: xplane interval union / dispatches)",
            buckets=DEFAULT_MS_BUCKETS,
        )
        self.metrics.register_collector(self._collect_metrics)
        # on-demand device capture (GET /profile): one armed/active
        # request at a time — HTTP threads arm under _prof_lock, the
        # loop thread starts/stops/attributes it at dispatch boundaries
        self._prof_lock = threading.Lock()
        self._profile: Optional[Dict[str, Any]] = None  # guarded_by: _prof_lock [writes]
        self._last_attr: Optional[Dict[str, Any]] = None
        # HBM-roofline accounting for the device-time attribution: one
        # decode forward streams the full weight tree plus its KV
        # working set — K forwards per scan dispatch.  DENSE: the whole
        # allocated buffer (XLA attends the masked buffer; the Pallas
        # kernels clamp at the cursor, so the count is conservative
        # for them).  PAGED: the LIVE pages
        # only, read at roofline time — a forward reads exactly the
        # mapped pages through the table, so charging the full pool
        # would overstate bytes and flatter roofline_utilization on
        # lightly-loaded engines.  Shape/pool metadata only: never
        # touches (soon to be donated) device buffers.
        self._w_bytes = sum(
            int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(self.variables)
        )
        # dense engines only (paged readers derive their dense-view
        # counterfactual from the LIVE slot count at read time —
        # elastic slots make a constructor-time figure stale)
        self._kv_dense_bytes = (
            0 if self._layout is not None
            else sum(
                int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                for leaf in jax.tree.leaves(self._dstate["cache"])
            )
        )
        self._hbm_gbps = float(os.environ.get("MLCOMP_TPU_HBM_GBPS", "819"))
        self.step_count = 0
        # (chunk width, K) pairs whose fused program has COMPILED AND
        # RUN once (warmup or first-use warming) — tracked separately
        # from _fns because building the jit wrapper is not compiling
        # it; _dispatch_warmed is the plain-dispatch ladder's analogue
        self._fused_warmed: set = set()
        self._dispatch_warmed: set = set()
        self._stop = threading.Event()
        # watchdog state: _busy_since marks the host time the loop
        # thread entered a potentially-wedging call (dispatch issue,
        # output resolve, prefill chunk, insert); the monitor thread
        # declares a stall when it exceeds dispatch_stall_timeout.
        # _exit_loop asks the loop to die cleanly at its next boundary
        # (set by the watchdog after a stall so the restart path sees a
        # dead thread, never two live loops).
        self.dispatch_stall_timeout = (
            float(dispatch_stall_timeout)
            if dispatch_stall_timeout else None
        )
        self._busy_since: Optional[float] = None
        self._exit_loop = threading.Event()
        self._unhealthy_reason: Optional[str] = None
        # restart budget: one attempt per incident, but only if the
        # engine made progress (resolved a dispatch) since the last
        # restart — a crash loop stays down instead of flapping
        self._dispatches_at_restart: Optional[int] = None
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        self._watchdog: Optional[threading.Thread] = None
        if self.dispatch_stall_timeout is not None:
            self._watchdog = threading.Thread(
                target=self._watchdog_loop, daemon=True,
                name="engine-watchdog",
            )
            self._watchdog.start()

    @property
    def is_coordinator(self) -> bool:
        """True for single-host engines and for process 0 of a
        distributed serve gang — the process that owns the submit
        queue and broadcasts boundary decisions."""
        return self._dist is None or self._dist.is_coordinator

    def _dev(self, x, dtype=None):
        """Host->device upload, multi-process safe.  Single process:
        a plain ``jnp.asarray``.  In a distributed gang every process
        calls this with IDENTICAL bytes (the boundary broadcast is
        what guarantees it), and the upload must be a fully-REPLICATED
        global array or the SPMD programs reject the host-local
        input."""
        arr = np.asarray(x) if dtype is None else np.asarray(x, dtype)
        if not self._multiproc:
            return self._jnp.asarray(arr)
        from jax.sharding import NamedSharding, PartitionSpec

        return self._jax.make_array_from_process_local_data(
            NamedSharding(self.mesh, PartitionSpec()), arr
        )

    def _build_carry_shardings(self):
        """NamedSharding pytree matching ``_fresh_dstate``'s structure:
        KV bytes shard over the ``tp`` mesh axis at the kv-head axis
        (``cache/kv_store.HEAD_AXES``) when the head count divides,
        page tables and every bookkeeping row replicate.  The fresh
        carry is BORN with these shardings (jitted init with
        out_shardings) and every carry program re-pins them with a
        sharding constraint, so the donated chain reuses buffers
        instead of resharding — donation vectors must preserve
        shardings (graftcheck's ``donation-sharding`` rule is the
        static half of that contract)."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from mlcomp_tpu.cache.kv_store import HEAD_AXES, _leaf_name
        from mlcomp_tpu.models.generation import init_cache

        jax, mesh = self._jax, self.mesh
        tp = int(mesh.shape.get("tp", 1))
        rep = NamedSharding(mesh, P())

        def head_sharded(name: str, shape) -> Any:
            ax = HEAD_AXES.get(name)
            if ax is None or tp <= 1 or shape[ax] % tp:
                return rep
            parts: List[Any] = [None] * len(shape)
            parts[ax] = "tp"
            return NamedSharding(mesh, P(*parts))

        ns = self.slots
        sh: Dict[str, Any] = {}
        if self._layout is not None:
            # page arrays keep the dense axis order (page axis replaces
            # batch), so the dense head axis index carries over
            sh["pages"] = [
                head_sharded(s.keystr.rsplit("/", 1)[-1], s.shape)
                for s in self._layout.kv_specs
            ]
            sh["table"] = rep
            sh["cache_scalars"] = [
                rep for s in self._layout.leaves if s.slot_axis is None
            ]
        else:
            cache_abs = jax.eval_shape(
                lambda: init_cache(self.model, ns, self.l_buf)
            )
            sh["cache"] = jax.tree_util.tree_map_with_path(
                lambda path, leaf: head_sharded(
                    _leaf_name(path), leaf.shape
                ),
                cache_abs,
            )
        for key in ("last_logits", "presence", "cursors", "kv_start",
                    "positions", "active", "remaining", "eos", "t",
                    "k", "p", "rp", "rng", "rseed"):
            sh[key] = rep
        return sh

    def _fresh_dstate(self) -> Dict[str, Any]:
        """ALL decode state lives on device and is carried (donated)
        through the dispatch/insert programs: a steady-state dispatch
        is ONE device call plus ONE packed output fetch — no per-step
        knob-row uploads, no host-side rng split.  (The round-4
        engine made ~10 small host->device transfers per step, a
        syscall each; carrying the state cuts a dispatch to a single
        call.  The saving is not measured on this chip.)  The host keeps a _Slot mirror
        purely for bookkeeping (futures, streams, emitted tokens).
        Factored out of __init__ so a watchdog restart can rebuild the
        carry from scratch (a crashed loop may have died mid-donation,
        leaving the old pytree invalid).

        With explicit carry shardings (sharded paged / distributed
        engines) the carry is built INSIDE a jitted initializer with
        ``out_shardings`` — born sharded, and in a multi-process gang
        born as global arrays (a host-local ``jnp.zeros`` cannot feed
        a global-mesh program)."""
        if self._carry_shardings is None:
            return self._dstate_build()
        if "fresh_dstate" not in self._fns:
            self._fns["fresh_dstate"] = self._jax.jit(
                self._dstate_build, out_shardings=self._carry_shardings
            )
        return self._fns["fresh_dstate"]()

    def _dstate_build(self) -> Dict[str, Any]:
        jax, jnp = self._jax, self._jnp
        from mlcomp_tpu.models.generation import init_cache

        ns = self.slots
        if self._layout is not None:
            # PAGED carry: the KV bytes live in slot-count-independent
            # page arrays addressed through a per-slot table; the
            # non-KV cache leaves (cache_index scalars) ride separately
            # so the gather can rebuild the exact dense pytree the
            # dispatch core consumes.  Fresh tables map every row to
            # the graveyard (an unused row's frozen-cursor write must
            # never land on the shared zero page).
            from mlcomp_tpu.kvpool import GRAVE_PAGE

            cache_kv = {"pages": self._layout.fresh_pages()}
            cache_kv["table"] = jnp.full(
                (ns, self._layout.max_pages), GRAVE_PAGE, jnp.int32
            )
            cache_kv["cache_scalars"] = self._layout.scalars_of(
                init_cache(self.model, 1, self.l_buf)
            )
        else:
            cache_kv = {"cache": init_cache(self.model, ns, self.l_buf)}
        dstate = {
            **cache_kv,
            "last_logits": jnp.zeros((ns, self.vocab), jnp.float32),
            "presence": jnp.zeros((ns, self.vocab), jnp.bool_),
            "cursors": jnp.zeros((ns,), jnp.int32),
            "kv_start": jnp.zeros((ns,), jnp.int32),
            "positions": jnp.zeros((ns,), jnp.int32),
            "active": jnp.zeros((ns,), jnp.bool_),
            "remaining": jnp.zeros((ns,), jnp.int32),
            "eos": jnp.full((ns,), -1, jnp.int32),
            "t": jnp.zeros((ns,), jnp.float32),
            "k": jnp.full((ns,), self.vocab, jnp.int32),
            "p": jnp.ones((ns,), jnp.float32),
            "rp": jnp.ones((ns,), jnp.float32),
            "rng": jax.random.PRNGKey(self._seed),
            # per-slot REQUEST seed (the rid, set at insert): the scan
            # dispatch derives row r's sampling key for its token at
            # position p as fold_in(fold_in(rng, rseed[r]), p), so a
            # request's sampled stream depends only on (engine seed,
            # request, token index) — NEVER on how steps were grouped
            # into dispatches, when neighbours joined, or pipeline
            # depth.  This is what makes emitted tokens bit-identical
            # under any adaptive-K schedule; the greedy path never
            # reads it.
            "rseed": jnp.zeros((ns,), jnp.int32),
        }
        return dstate

    # ------------------------------------------------------------- public

    def submit(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: int,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        eos_id: Optional[int] = None,
        logprobs: bool = False,
        repetition_penalty: float = 1.0,
        stream: Optional["queue.Queue"] = None,
        deadline_s: Optional[float] = None,
        trace_id: Optional[str] = None,
        _count: bool = True,
    ) -> Future:
        ids = [int(t) for t in prompt_ids]
        if not ids:
            raise ValueError("prompt must be non-empty")
        n_new = int(max_new_tokens)
        if n_new <= 0:
            raise ValueError("max_new_tokens must be positive")
        if n_new > self.max_new_cap:
            raise ValueError(
                f"max_new_tokens {n_new} exceeds the engine cap "
                f"{self.max_new_cap}"
            )
        self._bucket(len(ids))  # validate now, in the caller thread
        if not self.is_coordinator:
            raise NotCoordinator(
                "this process is a follower in a distributed serve "
                "gang; submit to the coordinator (process 0)"
            )
        if self.prefill_only and stream is not None:
            raise ValueError(
                "a prefill_only engine emits no tokens to stream: the "
                "future resolves with the handoff payload (decode — "
                "and stream — on a decode replica via import_pages)"
            )
        if self._stop.is_set():
            # a submit racing close() must fail HERE — after close's
            # queue drain nobody reads the queue, so an enqueued request
            # would hold an unresolvable Future
            raise RuntimeError("decode engine closed")
        if self._broken is not None:
            raise RuntimeError(
                f"decode engine is down: {self._broken!r}"
            ) from self._broken
        if deadline_s is not None and float(deadline_s) <= 0:
            raise ValueError(
                f"deadline_s must be positive, got {deadline_s}"
            )
        # W3C-style trace context: every request carries a 32-hex trace
        # id from submit to finish — minted here unless the caller
        # (the HTTP layer inheriting a client's ``traceparent``)
        # supplies one.  The id rides the request object into every
        # flight-recorder span the request touches and is echoed in
        # the response, so one id follows a request across daemons.
        if trace_id is None:
            trace_id = make_trace_id()
        elif not valid_trace_id(trace_id):
            raise ValueError(
                f"trace_id must be 32 lowercase hex chars (W3C trace "
                f"context), got {trace_id!r}"
            )
        fut: Future = Future()
        # request-lifecycle trace: one async span per request
        # (queue -> admit -> first_token -> finish), correlated by rid.
        # Warmup's dummy submissions stay out of the recording like
        # they stay out of every other request-visible counter.
        rid = next(self._rid) if _count else 0
        fut.rid = rid  # the cancel(rid) handle callers key on
        fut.trace_id = trace_id  # echoed on every response path
        if rid:
            self.recorder.async_begin(
                "request", rid, cat="req", prompt=len(ids), n_new=n_new,
                trace_id=trace_id,
            )
        now = time.perf_counter()
        self._queue.put({
            "ids": ids, "n_new": n_new, "future": fut,
            "temperature": float(temperature),
            "top_k": self.vocab if top_k is None else int(top_k),
            "top_p": 1.0 if top_p is None else float(top_p),
            "eos_id": -1 if eos_id is None else int(eos_id),
            "logprobs": bool(logprobs),
            "repetition_penalty": float(repetition_penalty),
            "stream": stream,
            "t_submit": now,
            # absolute host deadline; the loop retires the request at
            # the first dispatch boundary past it (None = no deadline)
            "t_deadline": (
                None if deadline_s is None else now + float(deadline_s)
            ),
            "rid": rid,
            "trace_id": trace_id,
            # warmup's dummy prompts must not seed (or probe) the prefix
            # cache — they'd pin budget with [1]*bucket junk
            "warmup": not _count,
        })
        if self._stop.is_set() or self._broken is not None:
            # close() (or a dying loop) may have drained the queue
            # between the checks above and our put; resolve the future
            # ourselves (idempotent — see _fail_future; a duplicate
            # stream None is harmless, the consumer stops at the first)
            if stream is not None:
                stream.put(None)
            _fail_future(fut, self._broken or RuntimeError(
                "decode engine closed"
            ))
        if _count:
            # warmup's dummy submissions pass _count=False so the
            # service-visible request count means real requests only
            # graftcheck: ignore[unguarded-write] -- GIL-atomic int add; the sole off-loop writer, and the only writer of this key
            self._stats["requests"] += 1
        return fut

    def validate_handoff(self, blob: bytes):
        """Parse + geometry-validate a handoff blob against THIS
        engine's paged layout — every violation raises the typed
        :class:`~mlcomp_tpu.kvpool.transfer.HandoffError` BEFORE any
        page, lease, or slot is touched (the partial-transfer
        contract, chaoscheck scenario 10).  Returns the parsed
        ``(meta, last_logits, payloads)`` for :meth:`import_pages`."""
        from mlcomp_tpu.kvpool.transfer import HandoffError

        if self._pool is None:
            raise ValueError(
                "import_pages needs kv_layout='paged': the handoff's "
                "currency is pages in this engine's PagePool"
            )
        try:
            return self._validate_handoff(blob)
        except HandoffError:
            # typed-reject accounting, wherever the validation ran
            # (HTTP thread or a direct import_pages call)
            # graftcheck: ignore[unguarded-write] -- GIL-atomic int add; off-loop reject accounting, sole writer of this key
            self._stats["handoff_rejects"] += 1
            raise

    def _validate_handoff(self, blob: bytes):
        from mlcomp_tpu.kvpool.transfer import HandoffError, decode_handoff

        meta, logits, payloads = decode_handoff(blob)
        pool, layout = self._pool, self._layout
        T = int(meta.get("page_tokens") or 0)
        if T != pool.page_tokens:
            raise HandoffError(
                f"handoff pages hold {T} tokens; this pool's hold "
                f"{pool.page_tokens} — prefill and decode replicas "
                "must share the page quantum (kv_page_tokens)"
            )
        try:
            ids = [int(t) for t in meta["ids"]]
            s_bucket = int(meta["s_bucket"])
            start_pad = int(meta["start_pad"])
            n_new = int(meta["n_new"])
        except (KeyError, TypeError, ValueError) as e:
            raise HandoffError(f"bad handoff metadata: {e}") from None
        if not ids or n_new <= 0:
            raise HandoffError("handoff carries no prompt or no budget")
        if n_new > self.max_new_cap:
            raise HandoffError(
                f"handoff max_new_tokens {n_new} exceeds this engine's "
                f"cap {self.max_new_cap}"
            )
        try:
            want_bucket = self._bucket(len(ids))
        except ValueError as e:
            # a prompt past this engine's largest bucket is the same
            # shared-geometry violation, rejected TYPED like the rest
            raise HandoffError(
                f"handoff prompt does not fit this engine's buckets: "
                f"{e} — prefill and decode replicas must share prompt "
                "buckets"
            ) from None
        if s_bucket != want_bucket or (
            start_pad != s_bucket - len(ids)
        ):
            raise HandoffError(
                f"handoff placement (s_bucket={s_bucket}, "
                f"start_pad={start_pad}) does not match this engine's "
                f"bucket for a {len(ids)}-token prompt — prefill and "
                "decode replicas must share prompt buckets"
            )
        if s_bucket % T:
            raise HandoffError(
                f"s_bucket={s_bucket} is not page-aligned at T={T}"
            )
        n_pages = s_bucket // T - start_pad // T
        leaves = meta.get("leaves")
        if not isinstance(leaves, list) or len(leaves) != len(
            layout.kv_specs
        ) or len(payloads) != len(layout.kv_specs):
            raise HandoffError(
                f"handoff carries {len(payloads)} KV leaves; this "
                f"engine's cache has {len(layout.kv_specs)}"
            )
        for lv, spec, pl in zip(leaves, layout.kv_specs, payloads):
            want = (n_pages,) + layout._page_rest(spec)
            if lv.get("key") != spec.keystr:
                raise HandoffError(
                    f"handoff leaf {lv.get('key')!r} does not match "
                    f"this engine's {spec.keystr!r} (different model "
                    "or cache family)"
                )
            if tuple(pl.shape) != want or pl.dtype != np.dtype(
                spec.dtype
            ):
                raise HandoffError(
                    f"handoff leaf {spec.keystr}: payload "
                    f"{pl.dtype}{tuple(pl.shape)} vs expected "
                    f"{np.dtype(spec.dtype)}{want}"
                )
        if tuple(logits.shape) != (1, self.vocab):
            raise HandoffError(
                f"handoff logits shaped {tuple(logits.shape)}; this "
                f"engine's vocab row is (1, {self.vocab})"
            )
        return meta, logits, payloads

    def import_pages(
        self,
        blob: bytes,
        stream: Optional["queue.Queue"] = None,
        deadline_s: Optional[float] = None,
        trace_id: Optional[str] = None,
        parsed=None,
    ) -> Future:
        """Admit a request by IMPORTING its finished prefill — the
        decode half of a disaggregated handoff.  The payload pages are
        written straight into the PagePool at the admission boundary
        (registry-registered, ref-counted) and the slot starts
        decoding at the prompt's end; no prefill chunk ever runs, and
        the emitted tokens are bit-identical to a local admission of
        the same prompt (same KV bytes, same final logits, same
        per-request sampling stream).

        Validation happens HERE, on the caller thread: a truncated or
        geometry-mismatched blob raises the typed ``HandoffError``
        with zero pages/leases touched.  ``parsed`` (the tuple
        :meth:`validate_handoff` returned) skips a second parse when
        the HTTP layer already validated."""
        if self._dist is not None:
            raise RuntimeError(
                "import_pages does not compose with distributed "
                "serving yet (imports are not broadcast to the gang) "
                "— the named follow-up"
            )
        meta, logits, payloads = (
            parsed if parsed is not None
            else self.validate_handoff(blob)
        )
        if self._stop.is_set():
            raise RuntimeError("decode engine closed")
        if self._broken is not None:
            raise RuntimeError(
                f"decode engine is down: {self._broken!r}"
            ) from self._broken
        if deadline_s is not None and float(deadline_s) <= 0:
            raise ValueError(
                f"deadline_s must be positive, got {deadline_s}"
            )
        knobs = dict(meta.get("req") or {})
        if trace_id is None:
            trace_id = meta.get("trace_id")
        if trace_id is None or not valid_trace_id(trace_id):
            trace_id = make_trace_id()
        fut: Future = Future()
        rid = next(self._rid)
        fut.rid = rid
        fut.trace_id = trace_id
        self.recorder.async_begin(
            "request", rid, cat="req", prompt=len(meta["ids"]),
            n_new=int(meta["n_new"]), trace_id=trace_id, imported=True,
        )
        now = time.perf_counter()
        self._queue.put({
            "ids": [int(t) for t in meta["ids"]],
            "n_new": int(meta["n_new"]), "future": fut,
            "temperature": float(knobs.get("temperature", 0.0)),
            "top_k": int(knobs.get("top_k") or self.vocab),
            "top_p": float(knobs.get("top_p") or 1.0),
            "eos_id": int(
                knobs.get("eos_id") if knobs.get("eos_id") is not None
                else -1
            ),
            "logprobs": bool(knobs.get("logprobs", False)),
            "repetition_penalty": float(
                knobs.get("repetition_penalty", 1.0)
            ),
            "stream": stream,
            "t_submit": now,
            "t_deadline": (
                None if deadline_s is None else now + float(deadline_s)
            ),
            "rid": rid,
            "trace_id": trace_id,
            "warmup": False,
            # the parsed handoff rides the request into the loop; the
            # completion boundary writes the pages and inserts the slot
            "handoff": {
                "meta": meta, "logits": logits, "payloads": payloads,
                "bytes": len(blob) if blob is not None else 0,
            },
        })
        if self._stop.is_set() or self._broken is not None:
            if stream is not None:
                stream.put(None)
            _fail_future(fut, self._broken or RuntimeError(
                "decode engine closed"
            ))
        # graftcheck: ignore[unguarded-write] -- GIL-atomic int add; same off-loop requests accounting as submit()
        self._stats["requests"] += 1
        return fut

    def cancel(self, rid: int) -> bool:
        """Request cancellation of a live request by its rid (the
        ``rid`` attribute of the Future ``submit`` returned).  The loop
        retires it at the next dispatch boundary: queued requests fail
        without ever taking a slot, in-flight rows free their slot and
        their future fails with ``RequestCancelled``.  Returns True if
        the rid matched a live request (best-effort: a request may
        finish between the scan and the retirement)."""
        rid = int(rid)
        if rid <= 0:
            return False

        def is_live() -> bool:
            # the loop thread mutates _pending concurrently; a deque
            # iterated mid-mutation raises RuntimeError — retry, and
            # if it keeps churning assume live (cancel is best-effort,
            # and a rare stale rid is discarded by the sweep/finish)
            for _ in range(3):
                try:
                    if any(
                        sl is not None and sl.req.get("rid") == rid
                        for sl in self._host
                    ) or any(
                        req.get("rid") == rid
                        for req in list(self._pending)
                    ):
                        return True
                    break
                except RuntimeError:
                    continue
            else:
                return True
            adm = self._adm
            if adm is not None and adm.req.get("rid") == rid:
                return True
            with self._queue.mutex:  # not yet pumped out of the queue?
                return any(
                    isinstance(r, dict) and r.get("rid") == rid
                    for r in self._queue.queue
                )

        if not is_live():
            return False
        self._cancelled.add(rid)
        # close the finish race: if the request completed between the
        # scan and the add, nothing will ever sweep the rid out (the
        # loop's discards ran before the add) — rids are never reused,
        # so a dead rid in the set would defeat the sweep's fast path
        # forever.  A finish AFTER the add is discarded by _finish /
        # _fail_queued themselves.
        if not is_live():
            self._cancelled.discard(rid)
            return False
        return True

    def profile(self, dispatches: int = 8,
                trace_dir: Optional[str] = None) -> Future:
        """Arm a windowed device-profile capture around the next
        ``dispatches`` dispatch boundaries (``GET /profile``).  The
        drive loop starts a ``jax.profiler`` trace at the next boundary
        with decode work (the ``utils/profile.StepProfiler`` window
        idiom, fed the resolved-dispatch count), stops it behind a real
        device barrier after N dispatches, parses the xplane with the
        dependency-free reader (``obs/devprof.py``), and resolves the
        returned Future with the attribution dict: ``device_time_ms``
        (interval union over device lanes), ``host_gap_ms`` (wall the
        device sat idle — dispatch cost, pipeline bubble, admission
        stall), the kernel-name breakdown, and per-dispatch-family
        roofline utilization.  The device spans also merge into the
        flight recorder as the ``engine.device`` track, so a
        ``GET /trace`` after the capture renders host issue/resolve
        spans aligned above the device programs they launched.

        One capture at a time (the profiler session is process-global):
        a concurrent second arm raises :class:`ProfileBusy` (HTTP 409).
        Capture failures fail THIS future only — never the fleet."""
        n = int(dispatches)
        if self._dist is not None:
            raise RuntimeError(
                "on-demand device capture does not compose with "
                "distributed serving yet (the window's drains and "
                "barriers run on one process only, which would "
                "desequence the gang) — profile a single-host daemon; "
                "the gang-wide capture is the sharded-serving PR's "
                "named follow-up"
            )
        if not 1 <= n <= 1024:
            # the xplane parse + track merge run ON the loop thread at
            # the window close (a deliberate, bounded stall — it is an
            # explicit operator request); the cap keeps that stall
            # proportionate.  8 dispatches already attribute well.
            raise ValueError(
                f"dispatches must be in [1, 1024], got {dispatches}"
            )
        if self._broken is not None:
            raise RuntimeError(
                f"decode engine is down: {self._broken!r}"
            ) from self._broken
        if self._stop.is_set():
            raise RuntimeError("decode engine closed")
        import tempfile

        from mlcomp_tpu.utils.profile import StepProfiler

        fut: Future = Future()
        with self._prof_lock:
            if self._profile is not None:
                raise ProfileBusy(
                    "a device-profile capture is already armed or in "
                    "flight; retry after it resolves"
                )
            d = trace_dir or tempfile.mkdtemp(prefix="mlcomp_devprof_")
            self._profile = {
                "n": n, "dir": d, "future": fut,
                "owns_dir": trace_dir is None,
                "profiler": StepProfiler(d, start_step=0, num_steps=n),
                "families": {}, "t0": None, "t1": None, "resolved": 0,
            }
        if self._stop.is_set() or self._broken is not None:
            # close() (or a dying loop) may have run its profile drain
            # between the checks above and our arm — the same race
            # submit() re-checks after its enqueue.  Resolve ourselves
            # (idempotent: whoever also saw it loses the _fail race).
            self._finish_profile(
                error=self._broken or RuntimeError("decode engine closed")
            )
        return fut

    def profile_cancel(self, fut: Future) -> bool:
        """Best-effort disarm of a capture that has NOT started tracing
        (the HTTP layer's client-timeout path).  An active capture is
        never cancelled from outside — the loop thread owns the open
        trace and will close it at its window boundary."""
        with self._prof_lock:
            pr = self._profile
            if pr is None or pr["future"] is not fut:
                return False
            if pr["profiler"].active:
                return False
            self._profile = None
        _fail_future(fut, RuntimeError("profile capture cancelled"))
        if pr.get("owns_dir"):
            import shutil

            shutil.rmtree(pr["dir"], ignore_errors=True)
        return True

    @property
    def healthy(self) -> bool:
        """False once the drive loop is broken, abandoned, or dead
        (until a watchdog restart brings it back) — the bit behind
        /healthz's 503 and the ``mlcomp_engine_healthy`` gauge."""
        return (
            self._broken is None
            and not self._abandoned
            and self._thread.is_alive()
        )

    @staticmethod
    def _percentiles(samples) -> Optional[Dict[str, float]]:
        if not samples:
            return None
        p50, p95, p99 = np.percentile(
            np.asarray(samples, np.float64), [50, 95, 99]
        )
        return {"p50": round(float(p50), 3), "p95": round(float(p95), 3),
                "p99": round(float(p99), 3)}

    def stats(self) -> Dict[str, Any]:
        active = sum(1 for s in self._host if s is not None)
        out = {
            **self._stats,
            # queued = parked in the submit queue + pumped into the
            # loop's pending deque but not yet admitted
            "queue_depth": self._queue.qsize() + len(self._pending),
            "active_slots": active,
            "slots": self.slots,
            # the CURRENT dispatch depth (adaptive engines move it);
            # adaptive_k/k_ladder say whether and over what it moves
            "steps_per_dispatch": self.steps_per_dispatch,
            "adaptive_k": self.adaptive_k,
            "k_ladder": list(self.k_ladder),
            "prefill_chunk": self.prefill_chunk,
            "fused_admission": self.fused_admission,
            "kv_layout": self.kv_layout,
            "healthy": self.healthy,
        }
        if self.mesh is not None:
            # the /healthz mesh block: axis names/sizes, process
            # count/index, and whether THIS process fronts the gang —
            # what a fleet operator needs to see which daemon to
            # target and how the pod is carved up
            out["mesh"] = self._mesh_info()
        if self._pool is not None:
            out["live_slots"] = len(self._host)
            out["max_slots"] = self.max_slots
            out["kv_pool"] = self._pool_stats()
        out["watchdog"] = {
            "dispatch_stall_timeout_s": self.dispatch_stall_timeout,
            "stalls": self._stats["watchdog_stalls"],
            "restarts": self._stats["watchdog_restarts"],
            "unhealthy_reason": self._unhealthy_reason,
        }
        p = dict(self._pstats)  # snapshot: the loop thread mutates it
        done = self._stats["dispatches"]
        out["pipeline"] = {
            "depth": self.pipeline_depth,
            "inflight": len(self._inflight),
            "peak_inflight": p["peak_inflight"],
            "issued": p["issued"],
            # mean in-flight depth right after an issue: 1.0 = fully
            # synchronous, pipeline_depth = fully overlapped
            "occupancy": round(p["inflight_sum"] / p["issued"], 3)
            if p["issued"] else None,
            # completed admissions whose insert was enqueued behind an
            # unresolved dispatch (of stats()["prefills"]): ~all of
            # them where rows decode, 0 on an idle engine
            "inserts_behind_dispatch": p["inserts_behind_dispatch"],
            # the loop thread's ms per dispatch outside the blocked
            # fetch and the idle poll (the spans' boundary minus
            # resolve and idle_wait), the part of it spent while a
            # dispatch was in flight, and the ms blocked for outputs
            "host_ms_per_dispatch": round(p["host_ms"] / done, 3)
            if done else None,
            "host_hidden_ms_per_dispatch": round(p["hidden_ms"] / done, 3)
            if done else None,
            "resolve_wait_ms_per_dispatch": round(p["wait_ms"] / done, 3)
            if done else None,
            # hidden / host: 1.0 = the device had a dispatch queued
            # under every host millisecond
            "overlap_efficiency": round(p["hidden_ms"] / p["host_ms"], 4)
            if p["host_ms"] > 0 else None,
        }
        out["attention"] = {
            # slot rows that held a request at issue, over all rows of
            # all dispatches: the rest are handed an empty window and
            # cost the decode attention neither a fetch nor compute
            "rows_attended": p["rows_attended"],
            "rows_total": p["rows_total"],
            "rows_attended_share": round(
                p["rows_attended"] / p["rows_total"], 4
            ) if p["rows_total"] else None,
            # of the rows that went out empty, those a request was
            # waiting for at that issue (queued, or mid-prefill in the
            # admission lane): min(empty rows, requests waiting), summed
            # like the two above; total less attended less starved is
            # the rows nobody asked for
            "rows_starved": p["rows_starved"],
            # row writes of a token's K and V a layer: the attended
            # rows times the steps of their dispatch.  The int8 cache's
            # decode attention appends where it attends, so a row
            # without a request is not written; over rows_total x
            # steps it is the share of a write loop over every row
            # that is left
            "kv_rows_written": p["kv_rows_written"],
            # context tokens the live rows held at issue, summed over
            # attention layers and dispatches, and the part inside each
            # layer's window (min(context, window))
            "kv_tokens_attended": p["kv_attended"],
            "kv_tokens_live": p["kv_live"],
            "kv_tokens_attended_share": round(
                p["kv_attended"] / p["kv_live"], 4
            ) if p["kv_live"] else None,
            # tokens the dense int8 cache's decode attention moved from
            # HBM to read them: a window's first and last granule
            # rounded out to the walk's lane blocks and rungs
            # (decode_attention.kv_walk_counts over the windows of a
            # dispatch's first step), and attended over fetched; 0 and
            # None where no such walk runs
            "kv_tokens_fetched": p["kv_fetched"],
            "kv_fetch_live_share": round(
                p["kv_attended"] / p["kv_fetched"], 4
            ) if p["kv_fetched"] else None,
            # the trips that walk made for them, a granule a window
            # touches each (kv_walk_counts): fetched over trips is the
            # mean trip, thin rows (which the walk's ring of slots
            # keeps several of in flight) or whole granules
            "kv_trips": p["kv_trips"],
            # the same two sums split by layer kind: the layers with a
            # window, and the rest (whole-context layers)
            "by_kind": {
                "window": {
                    "kv_tokens_attended": p["kv_attended_window"],
                    "kv_tokens_live": p["kv_live_window"],
                },
                "full": {
                    "kv_tokens_attended":
                        p["kv_attended"] - p["kv_attended_window"],
                    "kv_tokens_live": p["kv_live"] - p["kv_live_window"],
                },
            },
        }
        out["admission"] = {
            # the one admission lane: ms it was held (the ``admission``
            # spans of the engine.lane track, summed: admit ->
            # inserted), the admissions that left it (inserted,
            # exported, failed or cancelled) and the loop iterations
            # they held it for, first and last included
            "lane_busy_ms": round(p["lane_busy_ms"], 3),
            "admissions": p["lane_admissions"],
            "boundaries": p["lane_boundaries"],
            # ms of boundaries at whose admission tick a request stayed
            # queued, by what the queue's head waited for: a slot (no
            # free row beyond the one the admission in the lane will
            # take), the lane (a row is free, the lane is another
            # request's), pages (both free, the page budget deferred
            # the head).  An ``admit`` instant's ``blocked_ms`` is the
            # same sums over that request's wait
            "blocked_ms": {
                "lane": round(p["blocked_lane_ms"], 3),
                "slot": round(p["blocked_slot_ms"], 3),
                "pages": round(p["blocked_pages_ms"], 3),
            },
        }
        # what this process compiled (utils/compiles.py: one listener a
        # process, so an engine built later starts above zero)
        out["programs"] = self._compiles.totals()
        # what the model's layers counted, summed over layers, steps
        # and chunks of every dispatch read back: a block a group
        counted = {
            group: {} for group, _, _ in self._count_entries
        }
        for (group, name, _), c in zip(self._count_entries, self._counts):
            counted[group][name] = float(c)
        for group in self._count_groups:
            block = group.block(
                counted[group.name],
                # rows holding a request at issue x steps x layers
                p["kv_rows_written"] * self._count_layers[group.name],
            )
            if block is not None:
                out[group.name] = block
        out["latency"] = {
            # "samples" is the WINDOW the percentiles summarize (the
            # deque, capped at its maxlen); "lifetime_samples" is the
            # true request count — on long runs the former saturates
            # and only the latter keeps growing
            "samples": len(self._lat_ttft),
            "lifetime_samples": self._lat_ttft_n,
            "ttft_ms": self._percentiles(self._lat_ttft),
            "per_token_ms": self._percentiles(self._lat_tok),
        }
        # device-time attribution: the last /profile capture's measured
        # split when one ran, else the cheap steady-state estimate —
        # the host-overhead/device split behind /healthz and the
        # roofline gauges
        out["device"] = self._device_summary()
        if self.prefix_cache is not None:
            out["prefix_cache"] = self.prefix_cache.stats()
        return out

    def _mesh_info(self) -> Dict[str, Any]:
        """The mesh block behind stats()/healthz and the mesh gauges.
        Tolerates placeholder mesh objects (construction-time tests):
        axis/device info degrades to None, the process/coordinator
        fields always answer."""
        try:
            axes = {str(k): int(v) for k, v in self.mesh.shape.items()}
            devices = 1
            for v in axes.values():
                devices *= v
        except Exception:
            axes, devices = None, None
        try:
            procs = int(self._jax.process_count())
            pidx = int(self._jax.process_index())
        except Exception:
            procs, pidx = 1, 0
        return {
            "axes": axes,
            "devices": devices,
            "processes": procs,
            "process_index": pidx,
            "coordinator": self.is_coordinator,
            "distributed": self._dist is not None,
        }

    def _pool_stats(self) -> Dict[str, Any]:
        """The page pool's stats with the HTTP-thread read race
        handled: the pool is loop-owned, and its reclaimable scan
        iterates dicts the loop may resize mid-read — retry, then fall
        back to the raw allocator counters (torn but shaped)."""
        for _ in range(3):
            try:
                return self._pool.stats()
            except RuntimeError:
                continue
        a = self._pool.alloc
        return {
            "pages_total": a.total_pages, "pages_free": a.free_pages,
            "pages_used": a.used_pages, **a.counters,
        }

    def _collect_metrics(self) -> None:
        """Scrape-time collector: snapshot the engine's monotonic
        stats into the registry (set_total keeps counters monotonic
        across scrapes) — the hot path pays nothing for /metrics."""
        m = self.metrics
        st = self._stats

        def ctr(name, help, value):
            m.counter(name, help).set_total(value)

        def gau(name, help, value):
            m.gauge(name, help).set(value)

        ctr("mlcomp_engine_requests_total",
            "Real (non-warmup) requests submitted", st["requests"])
        ctr("mlcomp_engine_dispatches_total",
            "Decode dispatches resolved", st["dispatches"])
        ctr("mlcomp_engine_steps_total",
            "Device decode forwards", st["steps"])
        ctr("mlcomp_engine_emitted_tokens_total",
            "Tokens emitted to requests", st["emitted_tokens"])
        ctr("mlcomp_engine_prefills_total",
            "Admissions completed (rows inserted)", st["prefills"])
        ctr("mlcomp_engine_prefill_chunks_total",
            "Prefill chunks run", st["prefill_chunks"])
        ctr("mlcomp_engine_fused_prefill_chunks_total",
            "Prefill chunks that rode a decode dispatch (subset of "
            "prefill_chunks)", st["fused_chunks"])
        ctr("mlcomp_engine_admissions_overlapped_total",
            "Completed admissions with at least one fused chunk",
            st["admissions_overlapped"])
        gau("mlcomp_engine_dispatch_k",
            "Decode steps per dispatch currently in effect (the "
            "adaptive controller's pick, or the pinned K)",
            self.steps_per_dispatch)
        ctr("mlcomp_engine_dispatch_k_changes_total",
            "Adaptive-K controller switches of steps_per_dispatch",
            st["dispatch_k_changes"])
        ctr("mlcomp_engine_latency_samples_total",
            "Requests behind the TTFT percentiles (lifetime)",
            self._lat_ttft_n)
        ctr("mlcomp_engine_deadline_exceeded_total",
            "Requests retired past their deadline",
            st["deadline_exceeded"])
        ctr("mlcomp_engine_cancelled_total",
            "Requests retired by cancel()", st["cancelled"])
        ctr("mlcomp_cache_degraded_total",
            "Prefix-cache faults contained to a cache-bypass",
            st["cache_degraded"])
        ctr("mlcomp_engine_watchdog_stalls_total",
            "Watchdog stall/dead-loop detections", st["watchdog_stalls"])
        ctr("mlcomp_engine_watchdog_restarts_total",
            "Drive-loop restarts the watchdog performed",
            st["watchdog_restarts"])
        gau("mlcomp_engine_healthy",
            "1 while the drive loop is alive and unbroken, else 0",
            1 if self.healthy else 0)
        if self.mesh is not None:
            info = self._mesh_info()
            gau("mlcomp_engine_mesh_devices",
                "Devices in the serving mesh (sharded engines only)",
                info["devices"] or 0)
            gau("mlcomp_engine_is_coordinator",
                "1 on the process that owns the submit queue (always "
                "1 single-host; process 0 of a distributed gang)",
                1 if info["coordinator"] else 0)
        gau("mlcomp_engine_slots", "Configured decode slots", self.slots)
        gau("mlcomp_engine_active_slots", "Slots currently decoding",
            sum(1 for s in self._host if s is not None))
        gau("mlcomp_engine_queue_depth", "Requests waiting for a slot",
            self._queue.qsize() + len(self._pending))
        p = dict(self._pstats)
        ctr("mlcomp_engine_pipeline_issued_total",
            "Dispatches issued into the pipeline", p["issued"])
        ctr("mlcomp_engine_pipeline_host_ms_total",
            "Loop-thread ms outside the blocked fetch and the idle poll",
            p["host_ms"])
        ctr("mlcomp_engine_pipeline_hidden_ms_total",
            "Host ms spent while a dispatch was in flight",
            p["hidden_ms"])
        ctr("mlcomp_engine_pipeline_wait_ms_total",
            "Host ms blocked on dispatch outputs", p["wait_ms"])
        ctr("mlcomp_engine_inserts_behind_dispatch_total",
            "Completed admissions whose insert was enqueued behind an "
            "unresolved dispatch (subset of prefills)",
            p["inserts_behind_dispatch"])
        ctr("mlcomp_engine_attention_rows_attended_total",
            "Slot rows holding a request at issue, summed over "
            "dispatches (the decode attention walks only these)",
            p["rows_attended"])
        ctr("mlcomp_engine_attention_rows_total",
            "Slot rows in the carry at issue, summed over dispatches",
            p["rows_total"])
        ctr("mlcomp_engine_attention_rows_starved_total",
            "Slot rows that went out empty while a request waited for "
            "one (queued, or mid-prefill in the admission lane), "
            "summed over dispatches", p["rows_starved"])
        ctr("mlcomp_engine_admission_lane_busy_ms_total",
            "Ms the one admission lane was held, admit -> inserted "
            "(the engine.lane track's admission spans)",
            p["lane_busy_ms"])
        blocked = m.counter(
            "mlcomp_engine_admission_blocked_ms_total",
            "Ms of loop boundaries at whose admission tick a request "
            "stayed queued, by what the queue's head waited for",
            labelnames=("reason",),
        )
        for reason in ("lane", "slot", "pages"):
            blocked.set_total(p[f"blocked_{reason}_ms"], reason=reason)
        made = self._compiles.totals()
        ctr("mlcomp_engine_programs_compiled_total",
            "Programs this process handed to the backend compiler (a "
            "persistent-cache hit counts, with its fetch time)",
            made["compiled"])
        ctr("mlcomp_engine_programs_compile_seconds_total",
            "Seconds of those backend compiles", made["compile_seconds"])
        ctr("mlcomp_engine_attention_kv_rows_written_total",
            "Rows whose new token a step's attention appended to the KV "
            "cache in each layer: rows holding a request at issue x the "
            "dispatch's steps", p["kv_rows_written"])
        ctr("mlcomp_engine_attention_kv_tokens_attended_total",
            "Context tokens inside each attention layer's window, summed "
            "over live rows, layers and dispatches", p["kv_attended"])
        ctr("mlcomp_engine_attention_kv_tokens_live_total",
            "Context tokens held by live rows, summed over layers and "
            "dispatches", p["kv_live"])
        ctr("mlcomp_engine_attention_kv_tokens_fetched_total",
            "Tokens the dense int8 cache's decode attention moved from "
            "HBM for the attended ones (whole lane blocks, rounded up "
            "to the walk's rungs), summed like kv_tokens_attended",
            p["kv_fetched"])
        ctr("mlcomp_engine_attention_kv_trips_total",
            "Trips that walk made to move them: a granule a live row's "
            "window touches each, summed like kv_tokens_fetched",
            p["kv_trips"])
        ctr("mlcomp_engine_attention_kv_tokens_attended_window_total",
            "The part of kv_tokens_attended on layers with a window",
            p["kv_attended_window"])
        ctr("mlcomp_engine_attention_kv_tokens_live_window_total",
            "The part of kv_tokens_live on layers with a window",
            p["kv_live_window"])
        for (group, name, what), value in zip(
            self._count_entries, self._counts
        ):
            ctr(f"mlcomp_engine_{group}_{name}_total", what, float(value))
        gau("mlcomp_engine_pipeline_depth", "Configured pipeline depth",
            self.pipeline_depth)
        gau("mlcomp_engine_pipeline_inflight",
            "Dispatches currently in flight", len(self._inflight))
        gau("mlcomp_engine_pipeline_peak_inflight",
            "Peak in-flight dispatch depth", p["peak_inflight"])
        gau("mlcomp_engine_pipeline_occupancy",
            "Mean in-flight depth right after an issue since start "
            "(1 = synchronous, the configured depth = fully overlapped)",
            p["inflight_sum"] / p["issued"] if p["issued"] else 0.0)
        gau("mlcomp_engine_pipeline_overlap_efficiency",
            "hidden_ms / host_ms since start",
            p["hidden_ms"] / p["host_ms"] if p["host_ms"] > 0 else 0.0)
        ctr("mlcomp_engine_trace_events_dropped_total",
            "Flight-recorder ring evictions", self.recorder.dropped)
        ctr("mlcomp_engine_profile_captures_total",
            "On-demand device-profile captures completed (/profile)",
            st["profile_captures"])
        dev = self._device_summary()
        if dev["device_time_ms_per_dispatch"] is not None:
            gau("mlcomp_engine_device_time_ms_per_dispatch",
                "Device-lane busy ms per dispatch (last capture, else "
                "the steady-state estimate: dispatch wall minus "
                "uncovered host work)",
                dev["device_time_ms_per_dispatch"])
        if dev["host_overhead_ms_per_dispatch"] is not None:
            gau("mlcomp_engine_host_overhead_ms_per_dispatch",
                "Non-device ms per dispatch (capture host gap, else "
                "the loop's host ms that no in-flight dispatch covered)",
                dev["host_overhead_ms_per_dispatch"])
        if dev["roofline_utilization"] is not None:
            gau("mlcomp_engine_roofline_utilization",
                "HBM-roofline dispatch time / measured device time "
                "(1.0 = decode runs at what the memory system can "
                "deliver)",
                dev["roofline_utilization"])
        if self._pool is not None:
            ps = self._pool_stats()
            gau("mlcomp_engine_kv_pages_total",
                "Allocatable device KV pages (paged layout; reserved "
                "NULL/GRAVE pages excluded)", ps.get("pages_total", 0))
            gau("mlcomp_engine_kv_pages_free",
                "Device KV pages on the free list", ps.get("pages_free", 0))
            gau("mlcomp_engine_kv_pages_shared",
                "Pages mapped by more than one reference (prefix "
                "sharing)", ps.get("pages_shared", 0))
            ctr("mlcomp_engine_kv_page_cow_forks_total",
                "Copy-on-write forks: shared prefix pages privately "
                "re-allocated because the slot's write span crossed "
                "the share boundary", ps.get("cow_forks", 0))
            ctr("mlcomp_engine_slots_scaled_total",
                "Elastic slot-count resizes (grow + shrink)",
                st["slots_scaled"])
            gau("mlcomp_engine_live_slots",
                "Current elastic slot count (floor = slots, cap = "
                "max_slots)", len(self._host))
            gau("mlcomp_engine_max_slots",
                "Elastic slot-count cap", self.max_slots)
            ctr("mlcomp_engine_kv_registry_hits_total",
                "Device prefix-page registry hits (shared pages mapped "
                "with no host round-trip)", ps.get("registry_hits", 0))
            ctr("mlcomp_engine_kv_registry_hit_tokens_total",
                "Prompt tokens whose prefill a registry hit skipped",
                st["kv_registry_hit_tokens"])
            ctr("mlcomp_engine_kv_pages_lazy_allocated_total",
                "Decode pages allocated lazily as cursors crossed page "
                "boundaries mid-stream (instead of worst-case at "
                "insert)", st["kv_pages_lazy_allocated"])
            ctr("mlcomp_engine_kv_decode_page_failures_total",
                "Requests failed mid-decode by a dry page pool at a "
                "lazy page crossing (bounded failure)",
                st["kv_decode_page_failures"])
            ctr("mlcomp_engine_handoffs_imported_total",
                "Disaggregated handoffs admitted via import_pages "
                "(prefill skipped; payload pages written straight "
                "into the pool)", st["handoffs_imported"])
            ctr("mlcomp_engine_kv_pages_imported_total",
                "KV pages received through handoff imports",
                st["kv_pages_imported"])
            ctr("mlcomp_engine_handoff_bytes_imported_total",
                "Handoff payload bytes received (wire size of "
                "accepted imports)", st["handoff_bytes_imported"])
            ctr("mlcomp_engine_handoff_rejects_total",
                "Handoff blobs rejected typed before any allocation "
                "(truncated transfer, geometry mismatch)",
                st["handoff_rejects"])
        if self.prefill_only:
            ctr("mlcomp_engine_handoffs_exported_total",
                "Completed admissions exported as page-payload "
                "handoffs (prefill-only engines)",
                st["handoffs_exported"])
            ctr("mlcomp_engine_kv_pages_exported_total",
                "KV pages serialized into exported handoffs",
                st["kv_pages_exported"])
            ctr("mlcomp_engine_handoff_bytes_exported_total",
                "Handoff payload bytes serialized (wire size of "
                "exports)", st["handoff_bytes_exported"])
        gau("mlcomp_engine_kv_bytes_moved_per_dispatch",
            "Estimated KV bytes one dispatch moves through HBM "
            "(dense: K forwards x buffer; paged fused: K forwards x "
            "live pages; paged lax sandwich: + the dense-view "
            "gather/scatter round trip)",
            self._kv_bytes_moved_per_dispatch())
        if self.prefix_cache is not None:
            cs = self.prefix_cache.stats()
            for key in ("lookups", "hits", "misses", "matched_tokens",
                        "used_hits", "used_hit_tokens", "inserted_tokens",
                        "evictions", "evicted_tokens", "insert_errors",
                        "insert_dropped"):
                ctr(f"mlcomp_prefix_cache_{key}_total",
                    f"Prefix KV cache {key.replace('_', ' ')}", cs[key])
            for key in ("bytes", "max_bytes", "nodes", "pinned_nodes",
                        "outstanding_leases", "capture_queue_depth"):
                gau(f"mlcomp_prefix_cache_{key}",
                    f"Prefix KV cache {key.replace('_', ' ')}", cs[key])

    def close(self, timeout: Optional[float] = 60.0) -> None:
        """Stop the step thread, then fail everything still in flight.

        Lifecycle contract (r4 verdict weak #4): shared engine state
        (slots, cache handles, futures of ACTIVE rows) is mutated only
        AFTER the step thread has provably exited — the loop is woken
        with a poison pill and joined.  If the thread does not exit
        within ``timeout`` (a dispatch wedged in the runtime), the
        engine is ABANDONED instead: ``_broken`` flips so submits fail
        fast, queued requests are failed (the queue is thread-safe),
        but slot/future state the thread may still touch is left alone
        — no mutate-while-running race, at the cost of active rows'
        futures resolving only if/when the wedged dispatch returns.
        """
        self._stop.set()
        self._queue.put(_POISON)  # wake a blocked queue.get NOW
        if self._dist is not None and not self._dist.is_coordinator:
            # a follower loop blocks in the boundary-channel recv, not
            # the queue: closing the channel is its poison pill
            self._dist.close()
        self._thread.join(timeout=timeout)
        if self._dist is not None:
            # coordinator: the loop's finally already broadcast the
            # stop record; release the sockets (idempotent)
            self._dist.close()
        if self._watchdog is not None:
            self._watchdog.join(timeout=5.0)
        if self.prefix_cache is not None:
            # drop queued captures (each pins a full admission cache's
            # device buffers) and stop the cache's worker thread
            self.prefix_cache.close()
        err = RuntimeError("decode engine closed")
        if self._thread.is_alive():
            # wedged mid-dispatch: force-detach LOUDLY (a silent leak
            # looked identical to a clean close), then do NOT touch
            # state the thread owns
            self._abandoned = True
            self._broken = RuntimeError(
                "decode engine close timed out; step thread abandoned"
            )
            self._unhealthy_reason = (
                f"close() join timed out after {timeout}s"
            )
            warnings.warn(
                f"decode engine close(): step thread did not exit "
                f"within {timeout}s (a dispatch is wedged in the "
                "runtime); abandoning it — active rows' futures "
                "resolve only if the dispatch ever returns",
                stacklevel=2,
            )
            self._drain_queue(err)
            pr = self._profile
            if pr is not None:
                # fail the waiter but leave profiler state alone: the
                # wedged loop still owns any open trace session
                _fail_future(pr["future"], self._broken)
            return
        # thread exited: nobody may be left waiting on a future/stream
        # that will never resolve — fail in-flight rows, the loop's
        # pending deque (safe now: its owner is dead), and the queue
        self._finish_profile(error=err)  # backstop; loop's drain is first
        for i in range(len(self._host)):
            self._finish(i, error=err)
        self._fail_admission(err)
        self._drain_pending(err)
        self._drain_queue(err)

    def _fail_admission(self, err: Exception) -> None:  # graftcheck: runs-on(loop)
        """Terminate the in-flight admission (if any): stream closed,
        future failed — the one teardown sequence every failure path
        shares."""
        if self._adm is None:
            return
        adm, self._adm = self._adm, None
        self._leave_lane(adm, time.perf_counter(), error=True)
        if adm.page_lease is not None:
            # a registry hit retained its source pages for the gather
            # + shared mapping; a dead admission must not pin them
            adm.page_lease.release()
            adm.page_lease = None
        if adm.req["stream"] is not None:
            adm.req["stream"].put(None)
        if adm.req.get("rid"):
            self._cancelled.discard(adm.req["rid"])
            self.recorder.async_end(
                "request", adm.req["rid"], cat="req", error=True,
            )
        _fail_future(adm.req["future"], err)

    def _drain_queue(self, err: Exception) -> None:
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            if req is _POISON:
                continue
            if "ctrl" in req:
                # a queued warm_on_loop record has a future but no
                # stream/rid — fail it directly (a _fail_queued would
                # KeyError and abort the drain mid-queue)
                _fail_future(req["future"], err)
                continue
            self._fail_queued(req, err)

    def _drain_pending(self, err: Exception) -> None:  # graftcheck: runs-on(loop)
        while self._pending:
            self._fail_queued(self._pending.popleft(), err)

    def _fail_queued(self, req: Dict[str, Any], err: Exception) -> None:
        """Fail a request that never reached a slot: stream closed,
        lifecycle span ended, future failed — shared by the close/break
        drains and the deadline/cancel sweep."""
        if req["stream"] is not None:
            req["stream"].put(None)
        if req.get("rid"):
            self._cancelled.discard(req["rid"])
            self.recorder.async_end(
                "request", req["rid"], cat="req", error=True,
            )
        _fail_future(req["future"], err)

    # ----------------------------------------------------------- programs

    def _bucket(self, n: int) -> int:
        return bucket(n, self.prompt_buckets, "prompt length")

    def _chunk_width(self, s_bucket: int) -> int:
        """The admission chunk width for a bucket: the configured
        ``prefill_chunk`` when it divides the bucket, else one
        monolithic chunk (odd buckets) — the ONE place this fallback
        rule lives (admission start, warmup, and both page-quantum
        derivations all read it)."""
        c = min(self.prefill_chunk, s_bucket)
        return s_bucket if s_bucket % c else c

    def _page_quantum(self, kv_page_tokens, why: str) -> int:
        """The page size the admission geometry admits: the gcd of
        every bucket's chunk width when ``kv_page_tokens`` is unset,
        else the explicit value validated to tile every chunk.  Both
        the paged decode pool and a prefill_only engine's EXPORT pages
        derive through here, so phase-split replicas launched from the
        same serve flags agree on the quantum by construction."""
        widths = {self._chunk_width(s) for s in self.prompt_buckets}
        T = (
            math.gcd(*widths) if kv_page_tokens is None
            else int(kv_page_tokens)
        )
        bad = sorted(c for c in widths if c % T)
        if bad:
            raise ValueError(
                f"kv_page_tokens={T} must divide every prefill chunk "
                f"width (got chunk(s) {bad}): {why}"
            )
        return T

    def _apply(self, *args, **kwargs):
        if self.quant_kernel:
            from mlcomp_tpu.ops.quant import quant_kernel_interception

            # norm folding mirrors generate()'s decode path (engine
            # greedy outputs must stay equal to generate's)
            with quant_kernel_interception(
                fold_norms=bool(
                    getattr(self.model, "fold_norms_eligible", False)
                )
            ):
                return self.model.apply(*args, **kwargs)
        return self.model.apply(*args, **kwargs)

    def _prefill_init_fn(self):
        """Fresh (B=1, l_buf) cache with every layer's cache_index
        pre-advanced to ``start_slot`` — the skipped all-pad chunks'
        K/V stay zero and their cache slots are invalid under kv_mask,
        so jumping the cursor over them is exact."""
        if "prefill_init" not in self._fns:
            jax, jnp = self._jax, self._jnp
            from mlcomp_tpu.models.generation import init_cache

            def pinit(start_slot):
                cache = init_cache(self.model, 1, self.l_buf)
                return jax.tree_util.tree_map_with_path(
                    lambda path, leaf: (
                        jnp.asarray(start_slot, leaf.dtype)
                        if path[-1].key == "cache_index" else leaf
                    ),
                    cache,
                )

            self._fns["prefill_init"] = jax.jit(pinit)
        return self._fns["prefill_init"]

    def _capture_fn(self, lo: int, s_bucket: int):
        """Device->host half of the prefix cache: the admission cache's
        slot rows [lo, s_bucket) per KV leaf.  ``lo`` is the
        admission's first RUN chunk boundary, so a cache-hit capture
        fetches only the rows its suffix chunks recomputed (the rows
        below came FROM the trie and never need to leave the device).
        Static chunk-aligned bounds keep the program count at most
        n_chunks per bucket."""
        key = ("capture", lo, s_bucket)
        if key not in self._fns:
            from mlcomp_tpu.cache.kv_store import slice_slot_rows

            self._fns[key] = self._jax.jit(
                lambda cache: slice_slot_rows(cache, lo, s_bucket)
            )
        return self._fns[key]

    def _prefill_init_cached_fn(self, width: int):
        """Host->device half of the prefix cache: a fresh (1, l_buf)
        cache with ``cache_index`` pre-advanced to ``start_slot`` AND
        the cached prefix rows written into slots [0, width).
        ``width`` is the chunk-aligned hit boundary (= start_slot), so
        the upload moves only the prefix span; the zero filler below
        ``start_pad`` lands on pad slots kv_mask keeps invalid."""
        key = ("prefill_init_cached", width)
        if key not in self._fns:
            from mlcomp_tpu.cache.kv_store import write_slot_rows

            # compose with the plain init (ONE owner of the
            # cache_index-advance contract) — cold and cached
            # admissions cannot diverge on it
            pinit = self._prefill_init_fn()

            def pinit_cached(start_slot, *rows):
                return write_slot_rows(pinit(start_slot), rows, width)

            self._fns[key] = self._jax.jit(pinit_cached)
        return self._fns[key]

    def _registry_rows_fn(self, width: int):
        """Device-to-device half of a prefix-REGISTRY hit (paged
        layout): slot rows [0, width) of every KV leaf gathered from
        the leased pages, in ``write_slot_rows`` order — feeds
        ``_prefill_init_cached_fn`` exactly like the host cache's
        assembled rows, minus the host round-trip."""
        key = ("registry_rows", width)
        if key not in self._fns:
            layout = self._layout
            self._fns[key] = self._jax.jit(
                lambda pages, ids: layout.gather_row_span(
                    pages, ids, width
                )
            )
        return self._fns[key]

    def warm_prefix_fns(self) -> int:
        """Precompile the prefix-cache programs (service warmup):
        every capture slice and cached prefill-init width per bucket.
        Cheap — unlike the prefill/dispatch programs these never trace
        the model (zeros-init + slice/scatter only), so compiling all
        n_chunks variants per bucket costs little, and the first real
        hit/capture mid-serving pays no compile stall."""
        if self.prefix_cache is None:
            return 0
        from mlcomp_tpu.cache.kv_store import kv_leaf_items
        from mlcomp_tpu.models.generation import init_cache

        jnp = self._jnp
        cache = init_cache(self.model, 1, self.l_buf)
        items = kv_leaf_items(cache)
        n = 0
        for s in self.prompt_buckets:
            c = self._chunk_width(s)
            for k in range(s // c):
                self._capture_fn(k * c, s)(cache)
                n += 1
                if k == 0:
                    continue  # width-0 insert can't happen (no hit)
                rows = []
                for _, axis, leaf in items:
                    shape = list(leaf.shape)
                    shape[axis] = k * c
                    rows.append(jnp.zeros(shape, leaf.dtype))
                self._prefill_init_cached_fn(k * c)(jnp.int32(k * c), *rows)
                n += 1
        return n

    def warm_export_fns(self) -> int:
        """Precompile the export capture programs (prefill-only
        service warmup): one chunk-aligned capture slice per possible
        pad placement per bucket.  Cheap like the prefix-cache
        programs — zeros-init + slice, never a model trace — so the
        first real handoff mid-serving pays no compile stall."""
        if not self.prefill_only:
            return 0
        if self.prefix_cache is not None:
            # warm_prefix_fns already ran the identical capture-warm
            # loop (the export reuses the cache's capture programs) —
            # don't execute every program a second time
            return 0
        from mlcomp_tpu.models.generation import init_cache

        cache = init_cache(self.model, 1, self.l_buf)
        n = 0
        for s in self.prompt_buckets:
            c = self._chunk_width(s)
            for k in range(s // c):
                self._capture_fn(k * c, s)(cache)
                n += 1
        return n

    def warm_dispatch_fns(self) -> int:
        """Precompile the K LADDER's plain dispatch programs (service
        warmup): one compile per rung on an adaptive engine, so a
        controller switch mid-serving is a dict lookup, never a
        loop-thread compile stall.  Pinned engines warm their one K.
        Runs on THROWAWAY carries — the donated input is a fresh
        ``_fresh_dstate`` the drive loop never owned."""
        if self.prefill_only:
            return 0  # no decode dispatch ever issues
        n = 0
        for k in self.k_ladder:
            if ("dispatch", k) in self._fns and k in self._dispatch_warmed:
                continue
            out = self._dispatch_fn(k)(self.variables, self._fresh_dstate())
            np.asarray(out[1].ravel()[0])  # block until it really ran
            self._dispatch_warmed.add(k)
            n += 1
        return n

    def warm_fused_fns(self) -> int:
        """Precompile the fused prefill+decode program per distinct
        chunk width — per ladder rung on adaptive engines — (service
        warmup).  Unlike the prefix-cache programs these DO trace the
        model, so each costs a real compile — paid here instead of on
        the loop thread at the first overlapped admission mid-serving.
        Runs on THROWAWAY state: the jit cache keys on shapes/dtypes,
        so a dummy call seeds it and nothing the drive loop owns is
        touched (safe to call while it idles)."""
        if not self.fused_admission:
            return 0
        jnp = self._jnp
        widths = {self._chunk_width(s) for s in self.prompt_buckets}
        n = 0
        for c in sorted(widths):
            for k in self.k_ladder:
                if (c, k) not in self._fused_warmed:
                    self._warm_fused_width(c, k)
                    n += 1
        return n

    def _warm_fused_width(self, c: int, k: Optional[int] = None) -> None:
        """Compile (and run once, on throwaway state) the fused program
        for chunk width ``c`` at dispatch depth ``k`` — the jit cache
        keys on shapes, so the dummy call seeds it and the real
        donating call never compiles.  Also the loop's first-use path
        (``_prep_fused_chunk``): there a compile failure stays
        ADMISSION-scoped — parity with the staged path, whose
        ``_prefill_chunk_fn`` compile errors only ever failed the
        joiner — because this call touches nothing the fleet depends
        on; only the real call's failure is engine-level (it donates
        the live carry)."""
        jnp = self._jnp
        if k is None:
            k = self.steps_per_dispatch
        out = self._fused_dispatch_fn(c, k)(
            self.variables, self._fresh_dstate(),
            self._prefill_init_fn()(self._dev(0, np.int32)),
            self._dev(np.zeros((1, c), np.int32)),
            self._dev(np.zeros((1, c), np.int32)),
            self._dev(np.ones((1, self.l_buf), bool)),
        )
        # block until it really ran — on the PACKED output, which is
        # replicated in a multi-process gang (the logits are not)
        np.asarray(out[1].ravel()[0])
        self._fused_warmed.add((c, k))

    def _prefill_chunk_fn(self, c: int):
        """One bounded prefill chunk: (1, c) tokens forward against the
        carried cache (the model's decode path handles i>0 chunked
        attention); returns the chunk's last-token logits + the cache.
        One program per distinct chunk width serves every chunk index
        and every prompt bucket that width divides.  The model computes
        logits for the chunk's last position only
        (``last_logits_only``): the whole (c, vocab) product was c
        times the work for the one row kept.  A non-last chunk needs
        no logits at all and still computes that row: dropping it
        would take a second program a width."""
        key = ("prefill_chunk", c)
        if key not in self._fns:
            jax, jnp = self._jax, self._jnp

            def pchunk(variables, cache, chunk, positions, kv_mask):
                logits, upd = self._apply(
                    {**variables, "cache": cache}, chunk, decode=True,
                    positions=positions, kv_mask=kv_mask,
                    mutable=["cache", "counters"], last_logits_only=True,
                )
                counts = _sown_counts(upd)
                out = (logits[:, -1].astype(jnp.float32), upd["cache"])
                return out if counts is None else out + (counts,)

            self._fns[key] = jax.jit(pchunk, donate_argnums=(1,))
        return self._fns[key]

    def _insert_fn(self):
        """Insert a prefilled row into the device state at a free slot.

        Everything per-slot (cache rows, logits, presence, cursor,
        position, window start, budget, sampling knobs) lands in ONE
        donated program; the scalars ride a single packed f32 row
        (ints < 2^24 round-trip exactly; an eos >= vocab never matches
        a sampled token, so f32 rounding of a huge eos is harmless)."""
        if "insert" not in self._fns:
            jax, jnp = self._jax, self._jnp
            layout = self._layout

            def insert(dstate, row_cache, row_logits, row_presence, packed,
                       *table_rows):
                slot = packed[0].astype(jnp.int32)
                out = dict(dstate)
                if layout is not None:
                    # PAGED: the prefilled row lands in the slot's
                    # PRIVATE pages only (write_sel routes shared and
                    # NULL entries to the graveyard — the shared prefix
                    # pages stay zero-copy references), and the slot's
                    # device table row flips from all-grave to the
                    # composed mapping.  cache_scalars stay the carry's,
                    # mirroring the dense insert keeping the engine's
                    # cache_index scalars (decode reads per-row cursors,
                    # never the global index).
                    trow, wsel = table_rows
                    out["pages"] = layout.insert_rows(
                        dstate["pages"], wsel, row_cache
                    )
                    out["table"] = dstate["table"].at[slot].set(trow)
                else:
                    out["cache"] = jax.tree.map(
                        lambda ec, rc: ec if rc.ndim == 0
                        else ec.at[slot].set(rc[0]),
                        dstate["cache"], row_cache,
                    )
                out["last_logits"] = dstate["last_logits"].at[slot].set(
                    row_logits[0]
                )
                out["presence"] = dstate["presence"].at[slot].set(
                    row_presence[0]
                )
                for i, (key, dt) in enumerate([
                    ("cursors", jnp.int32), ("positions", jnp.int32),
                    ("kv_start", jnp.int32), ("remaining", jnp.int32),
                    ("eos", jnp.int32), ("t", jnp.float32),
                    ("k", jnp.int32), ("p", jnp.float32),
                    ("rp", jnp.float32), ("rseed", jnp.int32),
                ]):
                    out[key] = dstate[key].at[slot].set(
                        packed[i + 1].astype(dt)
                    )
                out["active"] = dstate["active"].at[slot].set(True)
                return self._constrain_carry(out)

            # only dstate donates: the B=1 row buffers have no same-shape
            # output to reuse (donating them just emits warnings)
            self._fns["insert"] = jax.jit(insert, donate_argnums=(0,))
        return self._fns["insert"]

    def _deactivate_fn(self):
        """Retire ONE row on device (deadline/cancel): the device
        normally retires rows itself at EOS/budget, but a host-initiated
        retirement must clear ``active`` (and zero the budget) or the
        dead row keeps burning scan lanes until its slot is
        reused.  Composes onto the latest carry even with dispatches in
        flight — JAX sequences it after them on the device stream."""
        if "deactivate" not in self._fns:
            jax, jnp = self._jax, self._jnp

            def deact(dstate, slot):
                out = dict(dstate)
                out["active"] = dstate["active"].at[slot].set(False)
                out["remaining"] = dstate["remaining"].at[slot].set(0)
                return self._constrain_carry(out)

            self._fns["deactivate"] = jax.jit(deact, donate_argnums=(0,))
        return self._fns["deactivate"]

    def _clear_row_fn(self):
        """Repoint ONE slot's device page-table row to the graveyard
        (paged layout).  Must compose onto the carry BEFORE the slot's
        pages can be re-allocated: the retired row's frozen cursor
        keeps receiving each dispatch's K/V write, and the scatter
        writes back EVERY mapped page — a freed-then-reused page still
        mapped by the dead row would be corrupted by the dead row's
        write-back.  JAX sequences this after any in-flight dispatches
        and ahead of the next insert on the device stream."""
        if "clear_row" not in self._fns:
            jax, jnp = self._jax, self._jnp
            from mlcomp_tpu.kvpool import GRAVE_PAGE

            grave = jnp.full(
                (self._layout.max_pages,), GRAVE_PAGE, jnp.int32
            )

            def clear(dstate, slot):
                out = dict(dstate)
                out["table"] = dstate["table"].at[slot].set(grave)
                return self._constrain_carry(out)

            self._fns["clear_row"] = jax.jit(clear, donate_argnums=(0,))
        return self._fns["clear_row"]

    def _set_table_fn(self):
        """Rewrite the WHOLE device page table from the host mirror
        (lazy decode-page growth): ONE fixed-shape program per tick
        however many slots crossed a page boundary together — at peak
        short-stream concurrency whole cohorts cross in lockstep, and
        a per-slot program would serialize that many tiny dispatches
        onto the hot pre-issue boundary.  The mirror is authoritative
        (insert/retire/extend all write it first), and the table is
        (slots, max_pages) int32 — trivia next to one page.  Composes
        onto the donated carry like _clear_row_fn: JAX sequences it
        after in-flight dispatches (whose coverage was ensured at
        THEIR issue) and before the next one."""
        if "set_table" not in self._fns:
            jax = self._jax

            def set_table(dstate, table):
                out = dict(dstate)
                out["table"] = table
                return self._constrain_carry(out)

            self._fns["set_table"] = jax.jit(
                set_table, donate_argnums=(0,)
            )
        return self._fns["set_table"]

    def _lazy_extend_tick(self) -> None:  # graftcheck: runs-on(loop)
        """Page-granular LAZY decode allocation (paged layout): before
        each dispatch issues, make sure every live slot's mapping
        covers the cache slots the in-flight window can write —
        ``cursor + steps_hi * (inflight + 1) + 1``, capped at the
        row's span.  Pages are allocated only as cursors approach page
        boundaries, so admission control can overcommit the pool
        against decode budgets (the admit-more headline).  A dry pool
        here — after reclaiming registry pins — is the designed
        BOUNDED failure: the starved row fails typed
        (``NoFreePages``), frees its pages (often unblocking the next
        starved row in the same tick), and the fleet decodes on."""
        if self._pool is None:
            return
        from mlcomp_tpu.kvpool import NoFreePages

        pool = self._pool
        T = pool.page_tokens
        jnp = self._jnp
        # in-flight dispatches advance by the depth THEY were issued
        # at (adaptive K may have moved since); the dispatch about to
        # issue advances by the current one
        lookahead = sum(
            steps for _, _, _, steps in self._inflight
        ) + self.steps_per_dispatch + 1
        grew = False
        for i, sl in enumerate(self._host):
            if sl is None or sl.span_end is None:
                continue
            target = min(sl.span_end, sl.cursor + lookahead)
            if target <= sl.alloc_upto:
                continue
            p0 = sl.alloc_upto // T
            p1 = -(-target // T)
            try:
                try:
                    pool.extend_slot_row(i, p0, p1)
                except NoFreePages:
                    # registry pins are cache, not commitments
                    pool.reclaim(p1 - p0)
                    pool.extend_slot_row(i, p0, p1)
            except NoFreePages:
                self._stats["kv_decode_page_failures"] += 1
                self.recorder.instant(
                    "kv_page_exhausted", track="engine.loop", slot=i,
                    rid=sl.req.get("rid", 0),
                )
                err = NoFreePages(
                    f"KV page pool exhausted mid-decode: slot {i} "
                    f"needed {p1 - p0} page(s) at cursor {sl.cursor} "
                    "(lazy decode allocation overcommits the pool; "
                    "raise kv_pages or lower concurrency)"
                )
                # device first, then host — the same order the
                # deadline/cancel retirement uses
                self._dstate = self._deactivate_fn()(
                    self._dstate, self._dev(i, np.int32)
                )
                self._finish(i, error=err)
                self._release_slot_pages(i)
                continue
            self._stats["kv_pages_lazy_allocated"] += p1 - p0
            sl.alloc_upto = p1 * T
            grew = True
        if grew:
            # one whole-table write for however many rows grew this
            # tick (the host mirror is authoritative)
            self._dstate = self._set_table_fn()(
                self._dstate,
                self._dev(pool.tables[: len(self._host)]),
            )

    def _release_slot_pages(self, slot: int) -> None:  # graftcheck: runs-on(loop)
        """Live-path slot teardown (paged): grave the device table row,
        then release the host-side page references.  Called wherever a
        slot frees on the LIVE engine (natural finish, deadline/cancel
        retirement); the death/restart paths rebuild the whole carry
        and ``pool.reset()`` instead."""
        if self._pool is None:
            return
        self._dstate = self._clear_row_fn()(
            self._dstate, self._dev(slot, np.int32)
        )
        self._pool.free_slot(slot)

    # ------------------------------------------------------ elastic slots

    _PER_SLOT_KEYS = (
        "last_logits", "presence", "cursors", "kv_start", "positions",
        "active", "remaining", "eos", "t", "k", "p", "rp", "rseed",
    )

    def _slot_span(self, s_bucket: int, n_ids: int,
                   n_new: int) -> Tuple[int, int]:
        """A slot's WRITE span in cache-slot coordinates: real prompt
        tokens start at the left-pad boundary, decode writes run to the
        budget plus the scratch slot (a retired row's frozen cursor
        still receives each dispatch's write one past its last real
        slot).  Every page the span
        touches must be privately backed — pages fully inside the pad
        prefix (or past the span) map NULL and cost nothing."""
        start_pad = s_bucket - n_ids
        return start_pad, s_bucket + int(n_new) + 1

    def _pages_worst(self, req: Dict[str, Any]) -> int:
        """Worst-case pages a request can occupy (prefix sharing only
        ever reduces it) — the bound a request must fit INSIDE THE
        WHOLE POOL to be servable at all.  Since lazy decode
        allocation this is no longer the admission currency: see
        :meth:`_pages_initial`."""
        s_bucket = self._bucket(len(req["ids"]))
        start_pad, span_end = self._slot_span(
            s_bucket, len(req["ids"]), req["n_new"]
        )
        return self._pool.pages_needed(start_pad, span_end)

    def _alloc_end(self, s_bucket: int, span_end: int) -> int:
        """The slot span the INSERT must back with pages: the prefill
        content plus one dispatch of decode lookahead — everything
        past it allocates lazily as the cursor approaches
        (``_lazy_extend_tick``)."""
        return min(span_end, s_bucket + self.steps_per_dispatch + 1)

    def _pages_initial(self, req: Dict[str, Any]) -> int:
        """Pages a request needs AT ADMISSION under lazy decode
        allocation: its prefill span plus one dispatch of lookahead —
        the admission gate's currency since the fused-paged PR.
        Strictly <= the worst case, which is exactly why free-page
        admission control now admits more concurrent streams at equal
        HBM (the pool overcommits against decode budgets; a dry pool
        at a later page crossing is a BOUNDED failure, chaoscheck
        scenario 7)."""
        s_bucket = self._bucket(len(req["ids"]))
        start_pad, span_end = self._slot_span(
            s_bucket, len(req["ids"]), req["n_new"]
        )
        return self._pool.pages_needed(
            start_pad, self._alloc_end(s_bucket, span_end)
        )

    def _check_scale_fatblock(self, ns2: int) -> None:
        """The int8 fat-block cliff at SCALE time: elastic slots
        change the live row count at scale-up — warn (once) when a grow
        step pushes the decode GEMMs off the swept fat-block layout."""
        if not self.quant_kernel or self._fatblock_scale_warned:
            return
        from mlcomp_tpu.ops.pallas.quant_matmul import _GEMV_ROWS

        if ns2 > _GEMV_ROWS:
            self._fatblock_scale_warned = True
            warnings.warn(
                f"elastic scale-up to {ns2} slots puts as many rows "
                f"through the int8 kernels, past the "
                f"fat-block decode boundary (_GEMV_ROWS = {_GEMV_ROWS}): "
                "dispatches at this width fall onto prefill blocks at a "
                "measured ~2x per-call cost — cap max_slots "
                "to keep the row count within budget",
                stacklevel=2,
            )

    def _resize_fn(self, ns2: int):
        """Resize the PER-SLOT carry leaves to ``ns2`` rows: new rows
        get the same inactive defaults ``_fresh_dstate`` uses (all-grave
        table rows included — an unused row's frozen-cursor write must
        never land on the shared zero page); shrink slices, and is only
        ever run at full quiesce.  Pages, cache scalars, and the RNG
        stay OUT of the program — they are slot-count-independent, and
        every resized leaf changes shape so donation buys nothing."""
        key = ("resize", ns2)
        if key not in self._fns:
            jnp = self._jnp
            from mlcomp_tpu.kvpool import GRAVE_PAGE

            fills = {
                "last_logits": 0.0, "presence": False, "cursors": 0,
                "kv_start": 0, "positions": 0, "active": False,
                "remaining": 0, "eos": -1, "t": 0.0, "k": self.vocab,
                "p": 1.0, "rp": 1.0, "rseed": 0, "table": GRAVE_PAGE,
            }

            def resize(sub):
                out = {}
                ns = sub["active"].shape[0]
                for k2, leaf in sub.items():
                    if ns2 <= ns:
                        out[k2] = leaf[:ns2]
                    else:
                        pad = jnp.full(
                            (ns2 - ns,) + leaf.shape[1:], fills[k2],
                            leaf.dtype,
                        )
                        out[k2] = jnp.concatenate([leaf, pad], axis=0)
                return out

            self._fns[key] = self._jax.jit(resize)
        return self._fns[key]

    def _scale_slots(self, ns2: int) -> None:  # graftcheck: runs-on(loop)
        """Resize the live slot count (caller has drained the
        pipeline: in-flight packed outputs are shaped at the old
        width).  The dispatch/insert/deactivate programs re-trace at
        the new width on first use — a compile stall the watchdog's
        busy clock covers like any other."""
        ns = len(self._host)
        if ns2 == ns:
            return
        if ns2 > ns:
            self._check_scale_fatblock(ns2)
        keys = self._PER_SLOT_KEYS + (
            ("table",) if self._pool is not None else ()
        )
        self._busy_since = time.perf_counter()
        try:
            with self.recorder.span(
                "scale_slots", track="engine.loop", frm=ns, to=ns2,
            ):
                sub = {k2: self._dstate[k2] for k2 in keys}
                self._dstate = {
                    **self._dstate, **self._resize_fn(ns2)(sub),
                }
        finally:
            self._busy_since = None
        if ns2 > ns:
            self._host.extend([None] * (ns2 - ns))
        else:
            self._host = self._host[:ns2]
        self._stats["slots_scaled"] += 1

    def _elastic_tick(self) -> None:
        """Boundary maintenance for the elastic slot pool (paged only):
        GROW (doubling, capped at ``max_slots``) when traffic queues
        behind a full slot pool and the head request fits the free-page
        budget — so one long stream can no longer cap concurrency the
        pages could serve; SHRINK back to the construction floor at
        full quiesce so an idle engine re-traces nothing on the next
        trickle of traffic."""
        ns = len(self._host)
        if (self._adm is None and self._pending
                and None not in self._host and ns < self.max_slots):
            try:
                need = self._pages_initial(self._pending[0])
            except Exception:
                return  # a bad bucket surfaces at admission, not here
            if need <= self._pages_available(need):
                self._drain_inflight()
                self._scale_slots(min(self.max_slots, ns * 2))
        elif (ns > self._slots_floor and self._adm is None
                and not self._pending and not self._inflight
                and all(s is None for s in self._host)):
            self._scale_slots(self._slots_floor)

    def _pages_available(self, need: int) -> int:
        """Free pages, counting reclaimable registry pins only when the
        free list alone falls short: the reclaimable scan walks the
        whole registry, and this runs on the loop thread at every
        boundary with traffic pending — the unpressured common case
        must stay O(1)."""
        free = self._pool.alloc.free_pages
        if need <= free:
            return free
        return free + self._pool.reclaimable_pages()

    def _pop_admittable(self) -> Optional[Dict[str, Any]]:  # graftcheck: runs-on(loop)
        """The FIFO head of the pending deque, if it can be admitted at
        this boundary.  Dense: always.  Paged: the head must fit the
        free-page budget at its INITIAL need — prefill pages plus one
        dispatch of decode lookahead; later decode pages allocate
        lazily, which is what lets the pool overcommit against decode
        budgets and admit strictly more concurrent streams at equal
        HBM.  A short pool DEFERS the head (rows retiring free pages,
        so progress is guaranteed while anything decodes; FIFO order
        is preserved — no skip-ahead), and a request whose WORST case
        exceeds the whole pool fails immediately (it could never
        finish)."""
        if self._pool is None:
            return self._pending.popleft()
        from mlcomp_tpu.kvpool import NoFreePages

        req = self._pending[0]
        pool = self._pool
        worst = self._pages_worst(req)
        if worst > pool.alloc.total_pages:
            self._pending.popleft()
            self._fail_queued(req, NoFreePages(
                f"request needs {worst} pages worst-case; the pool holds "
                f"{pool.alloc.total_pages} (raise kv_pages or shrink the "
                "request)"
            ))
            return None
        need = self._pages_initial(req)
        if need > self._pages_available(need):
            return None
        return self._pending.popleft()

    def _dispatch_fn(self, k: Optional[int] = None):
        """K single-token steps in one lax.scan — one host dispatch and
        one host sync per K tokens (r4 verdict missing #1).  Per-row
        early exit: a row whose budget or EOS lands mid-scan stops
        emitting (``live`` masks its later steps), its cursor freezes so
        nothing writes past its allocation, and the returned state has
        it INACTIVE (the device retires rows; the host only does future
        bookkeeping).  K=1 is exactly the round-4 per-token step.

        Signature is (variables, dstate) -> (dstate', packed): the
        whole decode state is device-carried and donated, and the K
        steps' (tokens, logprobs, valid) come back as ONE (3, K, slots)
        f32 array — a steady-state dispatch moves no per-step operands
        host->device and fetches one buffer back (token ids < 2^24 are
        exact in f32).

        The family is K-KEYED: an adaptive engine cycles through a
        small warmed ladder of compiled programs (one per rung,
        precompiled by ``warm_dispatch_fns``) instead of recompiling —
        a K switch is a dict lookup at the next issue."""
        if k is None:
            k = self.steps_per_dispatch
        key = ("dispatch", k)
        if key not in self._fns:
            core = self._carry_core(k)
            if self._carry_shardings is None and not self._multiproc:
                self._fns[key] = self._jax.jit(
                    core, donate_argnums=(1,)
                )
            else:
                jax = self._jax

                def dispatch_sharded(variables, dstate):
                    out, packed = core(variables, dstate)
                    # donation must PRESERVE shardings: re-pin the
                    # carry to the shardings it was born with, so the
                    # donated chain aliases buffers instead of
                    # resharding mid-flight
                    out = self._constrain_carry(out)
                    packed = self._replicate_out(packed)
                    return out, packed

                self._fns[key] = jax.jit(
                    dispatch_sharded, donate_argnums=(1,)
                )
        return self._fns[key]

    def _constrain_carry(self, out):
        """Pin a carry-shaped output pytree to the engine's explicit
        carry shardings (no-op when propagation owns them)."""
        if self._carry_shardings is None:
            return out
        return self._jax.lax.with_sharding_constraint(
            out, self._carry_shardings
        )

    def _replicate_out(self, x):
        """Multi-process gangs read the packed token buffer back on
        EVERY host (np.asarray needs a fully-replicated global array);
        single-process engines gather whatever sharding XLA picked."""
        if not self._multiproc:
            return x
        from jax.sharding import NamedSharding, PartitionSpec

        return self._jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, PartitionSpec())
        )

    def _dispatch_core(self, k: int):
        """The raw ``(variables, dstate) -> (dstate', packed)`` dispatch
        body, the K-step scan, shared by the plain jitted dispatch AND
        the fused prefill+decode program family: the fused trace embeds
        this SAME function, so decode math, scan order, and the RNG stream are
        identical across the two paths by construction."""
        key = ("dispatch_core", k)
        if key not in self._fns:
            self._fns[key] = self._build_scan_dispatch_core(k)
        return self._fns[key]

    def _carry_core(self, k: int):
        """The dispatch body over the engine's CARRY layout: the raw
        core for the dense layout.  For the paged layout the carry is
        pages + table + cache scalars, and the data path is the
        ``MLCOMP_TPU_PAGED_ATTN`` knob's:

        - FUSED (auto/pallas, the hot path): the raw core itself runs
          paged — its attention reads K/V through the page table
          (``kvpool/attn``) and appends the new token's K/V into its
          page in place.  No dense view materializes; the carry passes
          straight through.
        - LAX (the reference/bisect sandwich): gather the dense view,
          run the DENSE core on it, scatter back — the PR-7 data path,
          kept everywhere as the correctness reference.

        Both are bit-identical to dense by construction (shared
        arithmetic / pure data movement) and by test.  Shared by the
        plain jitted dispatch AND the fused prefill+decode family,
        like the raw core itself."""
        if self._layout is None:
            return self._dispatch_core(k)
        key = ("carry_core", k)
        if key not in self._fns:
            core = self._dispatch_core(k)
            if self._paged_attn != "lax":
                # FUSED: the core consumes the paged carry directly
                self._fns[key] = core
                return core
            layout = self._layout
            impl = self._page_gather_impl

            def paged(variables, dstate):
                inner = {
                    k: v for k, v in dstate.items()
                    if k not in ("pages", "table", "cache_scalars")
                }
                inner["cache"] = layout.gather(
                    dstate["pages"], dstate["table"],
                    dstate["cache_scalars"], impl=impl,
                )
                out, packed = core(variables, inner)
                out2 = {k: v for k, v in out.items() if k != "cache"}
                out2["pages"] = layout.scatter(
                    dstate["pages"], dstate["table"], out["cache"]
                )
                out2["table"] = dstate["table"]
                out2["cache_scalars"] = layout.scalars_of(out["cache"])
                return out2, packed

            self._fns[key] = paged
        return self._fns[key]

    def _kv_fused(self) -> bool:
        """True when the dispatch cores run the FUSED paged data path
        (paged layout, ``MLCOMP_TPU_PAGED_ATTN`` != lax): the KV carry
        is the page tuple and attention goes through ``kvpool/attn``."""
        return self._layout is not None and self._paged_attn != "lax"

    def _kv_forward_fn(self, variables, dstate):
        """The model-forward adapter the dispatch cores thread their
        KV carry through: ``(kv, tok, positions, cursors, kv_mask) ->
        (logits, kv', counts)`` where ``kv`` is the dense cache pytree
        — or, fused-paged, the page TUPLE (the table is
        dispatch-invariant and closes over from the carry) — and
        ``counts`` is what the model's layers sowed (``_sown_counts``;
        None for a model that sows nothing, whose program is then what
        it always was)."""
        if not self._kv_fused():
            def forward(kv, tok, positions, cursors, kv_mask):
                logits, upd = self._apply(
                    {**variables, "cache": kv}, tok, decode=True,
                    positions=positions, kv_mask=kv_mask,
                    cache_cursor=cursors, mutable=["cache", "counters"],
                )
                return logits, upd["cache"], _sown_counts(upd)

            return forward
        from mlcomp_tpu.kvpool.attn import PagedKV, paged_kv

        layout = self._layout
        impl = "pallas" if self._paged_attn == "pallas" else "auto"
        gather_impl = self._page_gather_impl
        table = dstate["table"]

        def forward(kv, tok, positions, cursors, kv_mask):
            ctx = PagedKV(layout, kv, table, impl=impl,
                          gather_impl=gather_impl)
            with paged_kv(ctx):
                # no "cache" collection: the attention modules create
                # no dense cache variables under the context, so the
                # mutable pass-through is empty — pages come back via
                # the context
                logits, _ = self._apply(
                    dict(variables), tok, decode=True,
                    positions=positions, kv_mask=kv_mask,
                    cache_cursor=cursors, mutable=["cache"],
                )
            return logits, tuple(ctx.pages), None

        return forward

    def _fused_dispatch_fn(self, c: int, k: Optional[int] = None):
        """FUSED prefill+decode dispatch: one donated program that runs
        the usual dispatch body over all active slots AND one ``(1, c)``
        prefill chunk against the pending admission's carried cache.
        ``variables`` is a single shared argument, so parameters stream
        from HBM once per dispatch instead of once for decode plus once
        for a staged chunk, and the chunk costs no extra host dispatch
        at a drained boundary.  One program per distinct chunk width
        per scan K (one per ladder rung on adaptive engines) — the
        same compile budget shape as
        the staged ``_prefill_chunk_fn``, and like it the chunk half
        computes logits for its last position only, on a non-last
        chunk too (a program without them would be a second one a
        width and K)."""
        if k is None:
            k = self.steps_per_dispatch
        key = ("fused_dispatch", c, k)
        if key not in self._fns:
            jnp = self._jnp
            core = self._carry_core(k)

            def fused(variables, dstate, adm_cache, chunk, positions,
                      kv_mask):
                out, packed = core(variables, dstate)
                logits, upd = self._apply(
                    {**variables, "cache": adm_cache}, chunk, decode=True,
                    positions=positions, kv_mask=kv_mask,
                    mutable=["cache", "counters"], last_logits_only=True,
                )
                counts = _sown_counts(upd)
                if counts is not None:
                    packed = packed.at[-counts.shape[0]:].add(counts)
                out = self._constrain_carry(out)
                packed = self._replicate_out(packed)
                return (out, packed, logits[:, -1].astype(jnp.float32),
                        upd["cache"])

            # donate the decode carry AND the admission cache; the
            # chunk-invariant kv_mask (argnum 5) is reused across
            # chunks and must survive the call
            self._fns[key] = self._jax.jit(fused, donate_argnums=(1, 2))
        return self._fns[key]

    def _build_scan_dispatch_core(self, K: int):
        jax, jnp = self._jax, self._jnp
        from mlcomp_tpu.models.generation import sample_token_rowwise_keyed

        fused_kv = self._kv_fused()

        def dispatch(variables, dstate):
            # slot count from the CARRY, not the constructor: elastic
            # slots re-trace this same body at the new width
            rows = jnp.arange(dstate["active"].shape[0])
            kv_start = dstate["kv_start"]
            eos_row = dstate["eos"]
            t_row, k_row = dstate["t"], dstate["k"]
            p_row, rp_row = dstate["p"], dstate["rp"]
            slots_iota = jnp.arange(self.l_buf, dtype=jnp.int32)
            kv_mask = slots_iota[None, :] >= kv_start[:, None]
            # key the penalty machinery on LIVE rows: a finished
            # slot's stale rp must not keep the (slots, V) penalty
            # path running for everyone
            penalty_on = jnp.any((rp_row != 1.0) & dstate["active"])
            # the KV carry element: the dense cache pytree, or (fused
            # paged) the page tuple — attention then reads/writes
            # through the table via the kvpool context
            forward = self._kv_forward_fn(variables, dstate)
            # per-REQUEST sampling streams (K-schedule invariance):
            # row r's key for the token at position p is
            # fold_in(fold_in(rng, rseed[r]), p) — a pure function of
            # (engine seed, request, token index), so any grouping of
            # steps into dispatches samples identical tokens.  Greedy
            # rows never evaluate the keys (lax.cond in the sampler).
            req_keys = jax.vmap(
                lambda s: jax.random.fold_in(dstate["rng"], s)
            )(dstate["rseed"])

            def one_step(carry, _):
                (kv, last_logits, presence, cursors, positions,
                 live, remaining) = carry
                raw = last_logits

                def penalized():
                    rp = rp_row[:, None]
                    return jnp.where(
                        presence,
                        jnp.where(raw > 0, raw / rp, raw * rp), raw,
                    )

                adj = jax.lax.cond(penalty_on, penalized, lambda: raw)
                step_keys = jax.vmap(jax.random.fold_in)(
                    req_keys, positions
                )
                tok = sample_token_rowwise_keyed(
                    step_keys, adj, t_row, k_row, p_row
                )
                tok = jnp.where(live, tok, jnp.int32(self.pad_id))
                lp = jnp.take_along_axis(
                    jax.nn.log_softmax(raw, axis=-1), tok[:, None],
                    axis=-1,
                )[:, 0]
                presence = presence.at[rows, tok].max(live)
                remaining = jnp.where(live, remaining - 1, remaining)
                done_now = live & (
                    (tok == eos_row) | (remaining <= 0)
                )
                # a row that holds no request attends nothing: with
                # no valid slot its window is empty, so the attention
                # neither fetches nor computes its stale buffer (the
                # KV write at its frozen cursor stays)
                logits, kv2, counts = forward(
                    kv, tok[:, None], positions[:, None], cursors,
                    kv_mask & live[:, None],
                )
                carry2 = (
                    kv2, logits[:, -1].astype(jnp.float32),
                    presence,
                    jnp.where(live, cursors + 1, cursors),
                    jnp.where(live, positions + 1, positions),
                    live & ~done_now,
                    remaining,
                )
                if counts is None:
                    return carry2, (tok, lp, live)
                return carry2, (tok, lp, live, counts)

            kv0 = (
                tuple(dstate["pages"]) if fused_kv else dstate["cache"]
            )
            carry0 = (
                kv0, dstate["last_logits"],
                dstate["presence"], dstate["cursors"],
                dstate["positions"], dstate["active"],
                dstate["remaining"],
            )
            carry, (toks, lps, valid, *sown) = jax.lax.scan(
                one_step, carry0, None, length=K
            )
            out = dict(dstate)
            (kv_out, out["last_logits"], out["presence"],
             out["cursors"], out["positions"], out["active"],
             out["remaining"]) = carry
            if fused_kv:
                out["pages"] = list(kv_out)
            else:
                out["cache"] = kv_out
            packed = jnp.stack([
                toks.astype(jnp.float32),
                lps.astype(jnp.float32),
                valid.astype(jnp.float32),
            ])
            if sown:
                packed = _pack_counts(packed, sown[0].sum(axis=0))
            return out, packed

        return dispatch

    # ------------------------------------------------------- admission

    def _start_admission(self, req) -> None:  # graftcheck: runs-on(loop)
        """Begin a chunked prefill for ``req`` (a free slot exists —
        checked by the caller; slots only free up while it runs).

        An IMPORT request (``req["handoff"]``, the decode half of a
        disaggregated handoff) skips the whole prefill core: its KV
        already exists as page payloads, so the admission is born
        complete (``next_chunk == n_chunks``) and the loop's
        completion boundary — which drains the pipeline for an import
        — writes the pages and inserts the slot."""
        jnp = self._jnp
        ids = req["ids"]
        s_bucket = self._bucket(len(ids))
        if req.get("handoff") is not None:
            adm = _Admission(
                req, s_bucket, self._chunk_width(s_bucket), 0
            )
            adm.next_chunk = adm.n_chunks  # nothing to prefill
            adm.handoff = req["handoff"]
            self._take_lane(adm, time.perf_counter(), imported=True)
            req["cache_hit_tokens"] = 0
            self._adm = adm
            return
        c = self._chunk_width(s_bucket)
        start_pad = s_bucket - len(ids)
        first_chunk = start_pad // c  # all-pad chunks before are skipped
        adm = _Admission(req, s_bucket, c, first_chunk)
        row, rmask = left_pad_row(ids, s_bucket, self.pad_id)
        adm.row = row[None]
        # chunk-invariant operands once per admission: positions stay
        # host-side (each chunk uploads only its slice), the full-buffer
        # kv_mask uploads ONCE (a per-chunk (1, l_buf) upload is exactly
        # the small-transfer tax the device-carried state removed)
        adm.positions = np.maximum(
            np.cumsum(rmask.astype(np.int64)) - 1, 0
        ).astype(np.int32)[None]
        adm.kv_mask = self._dev(np.concatenate(
            [rmask[None], np.ones((1, self.l_buf - s_bucket), bool)], axis=1
        ))
        # prefix-cache lookup: a hit fetches the cached prefix's K/V
        # rows from host RAM into the fresh admission cache and jumps
        # the chunk cursor past them — prefill runs only on the
        # uncached suffix.  The hit is CHUNK-aligned (partial chunks
        # recompute; the boundary chunk rewrites its overlap with
        # identical bytes), and capped at len(ids)-1 so the final
        # token's chunk always runs and produces the sampling logits.
        # Stall honesty: the host assembly + upload below runs ON the
        # loop thread (the suffix chunk needs the rows), so a large
        # hit stalls active rows once for the assembly memcpy — more
        # than one chunk boundary, but far less than the skipped
        # chunks' total stall.  Overlapping the upload with dispatches
        # (an extra admission state) is the open follow-up.
        rid = req.get("rid", 0)
        tid = req.get("trace_id")
        t_lookup = time.perf_counter()
        self._take_lane(adm, t_lookup)
        hit_tokens = 0
        cache_faulted = False
        if self._pool is not None and not req.get("warmup"):
            # DEVICE prefix-page registry (kvpool): a placement-exact
            # hit maps the registered prompt-prefix pages straight into
            # the admission — the prefix rows are gathered DEVICE-TO-
            # DEVICE into the fresh admission cache (no host assemble,
            # no host->device upload), the chunk cursor jumps past
            # them, and at insert the same physical pages map into the
            # slot's table copy-on-write (ref-count bump, zero HBM copy
            # of the persistent K/V).  Misses fall through to the host
            # prefix cache below — the cross-placement tier that
            # re-places token-indexed blocks.  Faults degrade to a cold
            # prefill exactly like the host cache's — the registry sits
            # on the same ``cache.lookup`` chaos surface, and a fault
            # bypasses BOTH tiers for this admission (the tiers share
            # the lookup machinery; containment means going cold, not
            # retrying the fault one layer down).
            try:
                with self.recorder.span(
                    "kv_registry.lookup", track="engine.loop",
                    prompt=len(ids), rid=rid, trace_id=tid,
                ) as sp:
                    _inject_fault("cache.lookup")
                    lease = self._pool.registry_lookup(
                        s_bucket, start_pad, ids
                    )
                    if lease is not None:
                        # attach BEFORE the fallible gather calls: every
                        # failure path (the except below, a later
                        # _fail_admission) releases adm.page_lease — a
                        # lease dangling in a local would pin its pages
                        # forever
                        adm.page_lease = lease
                        p = min(lease.matched, len(ids) - 1)
                        cached_chunk = (start_pad + p) // c
                        if cached_chunk > first_chunk:
                            width = cached_chunk * c
                            hit_tokens = width - start_pad
                            n_pages = -(-width // self._pool.page_tokens)
                            rows = self._registry_rows_fn(width)(
                                self._dstate["pages"],
                                self._dev(
                                    lease.entries[:n_pages], np.int32
                                ),
                            )
                            adm.cache = self._prefill_init_cached_fn(
                                width
                            )(self._dev(width, np.int32), *rows)
                            adm.next_chunk = cached_chunk
                        else:
                            lease.release()
                            adm.page_lease = None
                    sp["hit_tokens"] = hit_tokens
                if hit_tokens:
                    self._stats["kv_registry_hit_tokens"] += hit_tokens
            except Exception as e:
                if adm.page_lease is not None:
                    adm.page_lease.release()
                    adm.page_lease = None
                hit_tokens = 0
                cache_faulted = True
                adm.cache = None
                adm.next_chunk = first_chunk
                self._stats["cache_degraded"] += 1
                self.recorder.instant(
                    "cache_degraded", track="engine.loop", rid=rid,
                    error=f"{type(e).__name__}: {e}",
                )
        if (not hit_tokens and not cache_faulted
                and self.prefix_cache is not None
                and not req.get("warmup")):
            # one tracing idiom: the lookup (and, on a hit, the host
            # assembly + upload — the stall active rows actually pay)
            # is a structured span on the engine track, its outcome in
            # the span args (hit_tokens=0 is a recorded miss).  A fault
            # anywhere in the lookup/assemble/upload path is CONTAINED
            # to a cache-bypass: the admission falls back to a cold
            # prefill (degraded mode, counted) instead of failing the
            # request — the cache is an accelerator, never a
            # correctness dependency.
            try:
                with self.recorder.span(
                    "prefix_cache.lookup", track="engine.loop",
                    prompt=len(ids), rid=rid, trace_id=tid,
                ) as sp:
                    lease = self.prefix_cache.lookup(ids)
                    if lease is not None:
                        try:
                            adm.skip_capture = lease.tokens >= len(ids)
                            p = min(lease.tokens, len(ids) - 1)
                            cached_chunk = (start_pad + p) // c
                            if cached_chunk > first_chunk:
                                hit_tokens = cached_chunk * c - start_pad
                                rows = self.prefix_cache.assemble(
                                    lease, cached_chunk * c, start_pad,
                                    hit_tokens,
                                )
                                adm.cache = self._prefill_init_cached_fn(
                                    cached_chunk * c
                                )(
                                    jnp.int32(cached_chunk * c),
                                    *[jnp.asarray(r) for r in rows],
                                )
                                adm.next_chunk = cached_chunk
                        finally:
                            lease.release()
                    sp["hit_tokens"] = hit_tokens
                if hit_tokens:
                    self.prefix_cache.record_hit(hit_tokens)
            except Exception as e:
                hit_tokens = 0
                adm.cache = None  # cold fallback below rebuilds it
                adm.next_chunk = first_chunk
                adm.skip_capture = False
                self._stats["cache_degraded"] += 1
                self.recorder.instant(
                    "cache_degraded", track="engine.loop", rid=rid,
                    error=f"{type(e).__name__}: {e}",
                )
        req["cache_hit_tokens"] = hit_tokens
        if any(s is not None for s in self._host):
            # the lookup/assemble/upload above ran ON the loop thread
            # with rows decoding — that wall is admission stall (see
            # the stall-honesty note above; overlapping the upload is
            # the open follow-up)
            adm.stall_ms += (time.perf_counter() - t_lookup) * 1e3
        if adm.cache is None:
            adm.cache = self._prefill_init_fn()(
                self._dev(first_chunk * c, np.int32)
            )
        adm.capture_lo = adm.next_chunk * c
        self._adm = adm

    def _take_lane(self, adm: _Admission, t_admit: float,
                   **args) -> None:  # graftcheck: runs-on(loop)
        """``adm`` has the lane from ``t_admit`` on: the ``admit``
        instant, with how long the request sat in ``_pending``
        (``queued_ms``: from the stamp ``_park`` kept) and what that
        wait is booked under (``blocked_ms``: the cumulative sums less
        what ``_park`` found; they cover whole boundaries, so they sum
        to ``queued_ms`` less the stretch of this boundary before the
        admit).  A request handed straight to ``_start_admission``
        (tools, tests) was never parked and carries neither."""
        req = adm.req
        adm.t_admit = adm.t_booked = t_admit
        adm.boundary0 = self._boundary_n
        self._in_lane = adm
        if not req.get("rid"):
            return
        if "blocked0" in req:
            p = self._pstats
            lane0, slot0, pages0 = req["blocked0"]
            args.update(
                queued_ms=round((t_admit - req["t_parked"]) * 1e3, 3),
                blocked_ms={
                    "lane": round(p["blocked_lane_ms"] - lane0, 3),
                    "slot": round(p["blocked_slot_ms"] - slot0, 3),
                    "pages": round(p["blocked_pages_ms"] - pages0, 3),
                },
            )
        self.recorder.async_instant(
            "admit", req["rid"], cat="req", bucket=adm.s_bucket,
            trace_id=req.get("trace_id"), **args,
        )

    def _leave_lane(self, adm: _Admission, t_close: float,
                    **args) -> int:  # graftcheck: runs-on(loop)
        """``adm`` gives the lane up at ``t_close`` (inserted, exported,
        failed or cancelled): one ``admission`` span on the
        ``engine.lane`` track from the ``admit`` stamp, so the track IS
        the lane and its gaps are the lane standing free; the same two
        stamps close ``lane_busy_ms``.  Returns the loop iterations the
        admission held the lane, first and last included."""
        boundaries = self._boundary_n - adm.boundary0 + 1
        self._in_lane = None
        p = self._pstats
        p["lane_busy_ms"] += (t_close - adm.t_booked) * 1e3
        p["lane_admissions"] += 1
        p["lane_boundaries"] += boundaries
        req = adm.req
        rid = req.get("rid", 0)
        rec = self.recorder
        rec.complete(
            "admission", rec.to_trace_us(adm.t_admit),
            (t_close - adm.t_admit) * 1e6, track="engine.lane",
            rid=rid, trace_id=req.get("trace_id"), bucket=adm.s_bucket,
            chunks=adm.chunks_run, fused_chunks=adm.fused_chunks,
            boundaries=boundaries, caused_by=str(rid) if rid else None,
            **args,
        )
        return boundaries

    def _book_boundary(self, now: float) -> None:  # graftcheck: runs-on(loop)
        """Close the lane's books on the boundary that ends at ``now``
        (the stamp that closed its span): its length, less an
        ``idle_wait`` at its head, goes under what its admission tick
        named for the queue's head, and the lane's busy time is booked
        up to here so that a reader between two boundaries sees an
        admission still in the lane."""
        dt = (now - self._t_lane) * 1e3
        self._t_lane = now
        self._boundary_n += 1
        p = self._pstats
        if self._head_blocked is not None:
            p[self._head_blocked] += dt
            self._head_blocked = None
        adm = self._adm
        if adm is not None:
            p["lane_busy_ms"] += (now - adm.t_booked) * 1e3
            adm.t_booked = now

    def _run_admission_chunk(self) -> None:  # graftcheck: runs-on(loop)
        """Run ONE STAGED prefill chunk — its own dispatch at a drained
        boundary, the pre-fused behavior (``fused_admission=False``,
        admissions with no decode fleet to ride, and the tools'
        entry point) — and complete the admission after its last chunk.
        The fused path advances chunks inside ``_issue_dispatch``
        instead, so decode never waits on this call."""
        jnp = self._jnp
        adm = self._adm
        c = adm.chunk
        lo = adm.next_chunk * c
        decoding = any(s is not None for s in self._host)
        t0 = time.perf_counter()
        self._busy_since = t0
        try:
            with self.recorder.span(
                "prefill_chunk", track="engine.loop",
                chunk=adm.next_chunk, of=adm.n_chunks,
                rid=adm.req.get("rid", 0), fused=False,
                trace_id=adm.req.get("trace_id"),
            ):
                logits, adm.cache, *sown = self._prefill_chunk_fn(c)(
                    self.variables, adm.cache,
                    self._dev(adm.row[:, lo:lo + c]),
                    self._dev(adm.positions[:, lo:lo + c]),
                    adm.kv_mask,
                )
                # read with the next dispatch's packed buffer, not here
                self._counts_pending.extend(sown)
        finally:
            self._busy_since = None
        if decoding:
            # a staged chunk dispatch with rows decoding IS the stall
            # the fused path removes
            adm.stall_ms += (time.perf_counter() - t0) * 1e3
        adm.last_logits = logits
        adm.next_chunk += 1
        adm.chunks_run += 1
        self._stats["prefill_chunks"] += 1
        if adm.next_chunk >= adm.n_chunks:
            with self._admission_complete_span(adm):
                self._complete_admission()

    def _prep_fused_chunk(self, adm: _Admission) -> Tuple[Any, Any]:
        """Host half of a fused chunk: slice and upload this chunk's
        token/position rows for ``_issue_dispatch``.  The
        ``engine.fused_prefill`` chaos point fires here — anything that
        fails BEFORE the combined device call is admission-scoped (the
        decode carry is untouched), and the boundary falls back to a
        plain decode dispatch.  The first use of a chunk width warms
        its fused program on throwaway state HERE, so a compile
        failure fails only the joiner (service warmup normally
        precompiles and makes this a set lookup)."""
        _inject_fault("engine.fused_prefill")
        if (adm.chunk, self.steps_per_dispatch) not in self._fused_warmed:
            # compile is busy time to the watchdog, like every other
            # potentially-wedging device call on this thread
            self._busy_since = time.perf_counter()
            try:
                self._warm_fused_width(adm.chunk, self.steps_per_dispatch)
            finally:
                self._busy_since = None
        c = adm.chunk
        lo = adm.next_chunk * c
        return (self._dev(adm.row[:, lo:lo + c]),
                self._dev(adm.positions[:, lo:lo + c]))

    # ------------------------------------------- loop spans and clock

    def _loop_span(self, name: str, t_open: float,
                   t_close: Optional[float] = None, **args) -> float:
        """One span on the ``engine.loop`` track between two
        ``perf_counter`` stamps, and the closing stamp back.  The
        spans that tile a boundary (``maintenance``, ``admission_tick``,
        ``issue``, ``resolve``, ``unpack``) hand that stamp to the next
        one as its opening, so consecutive spans leave no gap and a
        reader's self time (duration minus children) is exact."""
        if t_close is None:
            t_close = time.perf_counter()
        rec = self.recorder
        rec.complete(
            name, rec.to_trace_us(t_open), (t_close - t_open) * 1e6,
            track="engine.loop", **args,
        )
        return t_close

    def _account(self, now: float,
                 into: Optional[str] = "host_ms") -> None:  # graftcheck: runs-on(loop)
        """Book the loop thread's time since the last stamp into
        ``_pstats``: ``host_ms`` (and ``hidden_ms`` too when a dispatch
        was in flight over that stretch — call BEFORE ``_inflight``
        changes), ``wait_ms`` for resolve's blocked fetch, ``None`` for
        the idle poll (waiting for traffic is nobody's cost)."""
        dt = (now - self._t_acct) * 1e3
        self._t_acct = now
        if into is None:
            return
        p = self._pstats
        p[into] += dt
        if into == "host_ms" and self._inflight:
            p["hidden_ms"] += dt

    @contextmanager
    def _idle_wait(self):  # graftcheck: runs-on(loop)
        """The ``idle_wait`` span: a blocked wait for traffic on an idle
        engine, kept out of ``host_ms``."""
        t = time.perf_counter()
        self._account(t)
        try:
            yield
        finally:
            self._t_lane = self._loop_span("idle_wait", t)
            self._account(self._t_lane, None)

    def _admission_complete_span(self, adm: _Admission):
        """The ``admission_complete`` span: the admission's last
        boundary — the insert's enqueue (child ``insert``), or for an
        import / export the drain (``join_drain``) and the transfer."""
        return self.recorder.span(
            "admission_complete", track="engine.loop",
            rid=adm.req.get("rid", 0), chunks=adm.chunks_run,
            fused_chunks=adm.fused_chunks,
            trace_id=adm.req.get("trace_id"),
        )

    def _drain_inflight(self) -> None:  # graftcheck: runs-on(loop)
        """Resolve every in-flight dispatch (the recorded join_drain).
        Runs at LOOP level only: a dispatch failure surfacing here is
        an ENGINE-level error — the fleet's tokens are on the line, so
        it must reach the loop's fail-everything handler, never an
        admission-scoped except."""
        if not self._inflight:
            return
        with self.recorder.span(
            "join_drain", track="engine.loop",
            inflight=len(self._inflight),
        ):
            while self._inflight:
                self._process_oldest()

    # ------------------------------------------------- device profiling

    def _family_name(self, fused_chunk: Optional[int] = None) -> str:
        """The dispatch-program family a capture attributes to: the
        K-step scan, with the fused prefill+decode width as a suffix
        when an admission chunk rode the dispatch."""
        base = f"decode_scan_k{self.steps_per_dispatch}"
        if fused_chunk is not None:
            return f"{base}+prefill_c{fused_chunk}"
        return base

    def _profile_tick(self) -> None:  # graftcheck: runs-on(loop)
        """Loop-thread: advance the armed/active on-demand capture at
        this dispatch boundary.  Start only once there is decode work
        to record, at a clean boundary (in-flight dispatches from
        before the window drained); stop behind a device barrier after
        N dispatches — or early if traffic drained, reporting the
        dispatches that actually ran.  Capture failures are
        PROFILE-scoped (they fail the capture future, never the
        fleet); only the shared inflight drains may raise out of
        here, and those are genuinely engine-level."""
        pr = self._profile
        if pr is None:
            return
        prof = pr["profiler"]
        if not prof.active:
            # arm -> start once there is ANY device work to record: a
            # pending/in-progress admission counts (its prefill chunks
            # are device compute inside the window), not just live
            # decode rows — with short requests whose whole decode fits
            # one in-flight dispatch, waiting for live rows at a
            # boundary would never fire (the pre-window drain retires
            # the fleet every time)
            if not (self._adm is not None or self._pending
                    or any(s is not None for s in self._host)):
                return  # stay armed until traffic arrives
            self._drain_inflight()  # pre-window work resolves OUTSIDE
            start_err: Optional[Exception] = None
            with self._prof_lock:
                if self._profile is not pr:
                    return  # cancelled between the read and the start
                try:
                    prof.step(0)  # opens the jax.profiler trace window
                except Exception as e:
                    start_err = e
            if start_err is not None:
                self._finish_profile(error=start_err)
                return
            pr["t0"] = time.perf_counter()
            pr["resolved0"] = self._stats["dispatches"]
            self.recorder.instant(
                "profile_start", track="engine.loop", dispatches=pr["n"],
            )
            return
        resolved = self._stats["dispatches"] - pr["resolved0"]
        # idle mirrors the start gate: pending/in-progress admissions
        # are traffic too — a window must not close early while a
        # joiner is queued at this very boundary
        idle = (
            not self._inflight and self._adm is None
            and not self._pending
            and all(s is None for s in self._host)
        )
        # an open window closes when full — or early when traffic
        # drained, but only once it holds at least one dispatch
        if resolved < pr["n"] and not (idle and resolved > 0):
            if idle:
                # resolved == 0 and NOTHING left (no rows, admission,
                # pending, or inflight): the traffic that opened the
                # window was retired before a single dispatch resolved
                # (joiner deadline/cancel/failure).  Close and fail
                # rather than holding the process-global profiler
                # session — and every later /profile — hostage until
                # unrelated traffic arrives.
                self._finish_profile(error=RuntimeError(
                    "capture window closed empty: the traffic that "
                    "opened it was retired before any dispatch resolved"
                ))
            return
        self._drain_inflight()
        pr["resolved"] = self._stats["dispatches"] - pr["resolved0"]
        # block on the carry OURSELVES (a real device barrier — without
        # it the device would still be executing the profiled window
        # when the trace closes) and stamp t1 BEFORE the stop:
        # stop_trace's collection/serialization wall is neither
        # dispatch cost nor bubble, so it must not inflate host_gap_ms.
        # Busy time to the watchdog like every other potentially-
        # wedging device call on this thread.
        self._busy_since = time.perf_counter()
        try:
            self._jax.block_until_ready(self._dstate["last_logits"])
            pr["t1"] = time.perf_counter()
            prof.step(prof.stop_step)
        except Exception as e:
            self._finish_profile(error=e)
            return
        finally:
            self._busy_since = None
        self._finish_profile()

    def _finish_profile(self, error: Optional[Exception] = None) -> None:  # graftcheck: runs-on(loop)
        """Complete (or abort) the in-flight capture: close the trace
        window if still open, parse + attribute on success, clean the
        capture dir, resolve the future.  Never raises — it runs on
        every teardown path (loop death, close, parse failure)."""
        with self._prof_lock:
            pr, self._profile = self._profile, None
        if pr is None:
            return
        try:
            pr["profiler"].close()  # idempotent; stops an open trace
        except Exception as e:
            error = error or e
        if error is None and pr["future"].done():
            # the watchdog/abandon path already failed this waiter
            # while the window was wedged; the wedged dispatch then
            # returned and the loop closed the window normally.  The
            # wall is stall-inflated and no client will read it —
            # discard it rather than adopt it as the "capture"-sourced
            # ground truth behind /healthz and the roofline gauges.
            error = RuntimeError(
                "capture discarded: its waiter was already failed "
                "(watchdog stall verdict stands)"
            )
        attr = None
        if error is None:
            try:
                with self.recorder.span(
                    "profile_attribute", track="engine.loop",
                    dispatches=pr.get("resolved"),
                ):
                    attr = self._attribute_capture(pr)
            except Exception as e:
                error = e
        if pr.get("owns_dir"):
            import shutil

            shutil.rmtree(pr["dir"], ignore_errors=True)
        if error is not None:
            self.recorder.instant(
                "profile_error", track="engine.loop",
                error=f"{type(error).__name__}: {error}",
            )
            _fail_future(pr["future"], error)
            return
        self._last_attr = attr
        self._stats["profile_captures"] += 1
        per = attr.get("device_time_ms_per_dispatch")
        if per is not None:
            self._hist_device.observe(per)
        _set_result(pr["future"], attr)

    def _attribute_capture(self, pr: Dict[str, Any]) -> Dict[str, Any]:
        """Parse the capture's xplane and split the window into device
        compute vs host gap, per dispatch family.  Family device time
        is a PROPORTIONAL split by dispatch count — exact for the
        common single-family window, pro-rata for mixed ones (fused
        chunks next to plain dispatches)."""
        from mlcomp_tpu.obs import devprof

        planes = devprof.load_xspace(devprof.find_xplane(pr["dir"]))
        # wall ends at the last resolve's device fetch (t_last), not at
        # t1: the loop may have blocked in the idle queue pump between
        # the final resolve and _profile_tick, and that idle wait is
        # neither dispatch cost nor bubble — without this an
        # early-closed window inflates host_gap_ms by up to the pump
        # block (~200 ms) and the phantom overhead becomes the
        # capture-sourced "truth" behind /healthz and the gauges.
        wall_ms = (pr.get("t_last") or pr["t1"]) - pr["t0"]
        wall_ms *= 1e3
        # 48: a 16-layer dispatch fills twenty rows with per-layer
        # ``cond.N`` entries alone, which would push a prefill's flash
        # kernel out of the table
        att = devprof.attribution(planes, wall_ms=wall_ms, top_kernels=48)
        n = int(pr.get("resolved") or 0)
        att["dispatches"] = n
        att["requested_dispatches"] = pr["n"]
        roof_ms = self._roofline_ms()
        att["roofline_ms_per_dispatch"] = round(roof_ms, 4)
        dev, gap = att["device_time_ms"], att["host_gap_ms"]
        if n:
            per = dev / n
            util = round(roof_ms / per, 4) if per > 0 else None
            att["device_time_ms_per_dispatch"] = round(per, 4)
            att["host_gap_ms_per_dispatch"] = round(gap / n, 4)
            att["roofline_utilization"] = util
            total = sum(pr["families"].values()) or 1
            # per-family utilization only when it is EXACT (single-
            # family window): under the pro-rata split every family's
            # per-dispatch device time — hence util — would be the
            # same number, which reads as a measurement but isn't.
            # Mixed windows report null; the window-wide util above
            # stays the measured figure.
            fam_util = util if len(pr["families"]) == 1 else None
            att["families"] = {
                fam: {
                    "dispatches": c,
                    "device_time_ms": round(dev * c / total, 4),
                    "host_gap_ms": round(gap * c / total, 4),
                    "roofline_utilization": fam_util,
                }
                for fam, c in sorted(pr["families"].items())
            }
        else:
            att["device_time_ms_per_dispatch"] = None
            att["host_gap_ms_per_dispatch"] = None
            att["roofline_utilization"] = None
            att["families"] = {}
        self._merge_device_track(planes, pr)
        return att

    def _merge_device_track(self, planes, pr: Dict[str, Any]) -> None:
        """Fold the capture's device spans into the flight recorder as
        the named ``engine.device`` track: ``GET /trace`` then renders
        host issue/resolve spans ALIGNED above the device programs they
        launched, making pipeline bubbles and admission stalls visually
        attributable.  Alignment anchors the earliest device event at
        the capture's start on the recorder clock (host and device
        clocks share no epoch; the capture window is the common
        reference, good to ~the start_trace latency)."""
        from mlcomp_tpu.obs import devprof

        spans, dropped = devprof.device_spans_us(planes)
        if not spans or pr.get("t0") is None:
            return
        base_us = self.recorder.to_trace_us(pr["t0"])
        for ts, dur, name in spans:
            self.recorder.complete(
                devprof.short_op(name), base_us + ts, dur,
                track="engine.device",
            )
        self.recorder.instant(
            "device_capture", track="engine.device",
            dispatches=pr.get("resolved"), spans=len(spans),
            dropped=dropped,
        )

    def _device_summary(self) -> Dict[str, Any]:
        """The device/host split behind ``stats()["device"]`` and the
        roofline gauges: the last capture's measured attribution when
        one exists, else the cheap steady-state ESTIMATE from the loop
        thread's own books (``_account``): the dispatch wall is the
        loop's busy time per dispatch (``host_ms + wait_ms``), the host
        overhead the part of ``host_ms`` that no in-flight dispatch
        covered (``host_ms − hidden_ms``: the estimate's stand-in for a
        capture's host gap), and the device time what is left.  The
        estimate is honest only when the pipeline saturates (an
        in-flight dispatch is then a running one); captures are ground
        truth."""
        p = dict(self._pstats)
        done = self._stats["dispatches"]
        roof_ms = self._roofline_ms()
        ss = None
        if done:
            wall = (p["host_ms"] + p["wait_ms"]) / done
            host = (p["host_ms"] - p["hidden_ms"]) / done
            dev_est = max(wall - host, 0.0)
            ss = {
                "dispatch_wall_ms": round(wall, 3),
                "host_overhead_ms": round(host, 3),
                "device_time_ms_est": round(dev_est, 3),
                "roofline_utilization_est": (
                    round(roof_ms / dev_est, 4) if dev_est > 0 else None
                ),
            }
        cap = self._last_attr
        per = host_ms = util = None
        if cap is not None:
            per = cap.get("device_time_ms_per_dispatch")
            host_ms = cap.get("host_gap_ms_per_dispatch")
            util = cap.get("roofline_utilization")
            # stats()/healthz recur (the report proxy re-serializes
            # every scrape): carry the capture's summary numbers, not
            # its parse products (top-20 kernels, plane/lane
            # inventory) — the full dict went to the /profile caller
            cap = {
                k: v for k, v in cap.items()
                if k not in (
                    "kernels", "planes", "device_lanes", "device_events"
                )
            }
        if per is None and ss is not None:
            per = ss["device_time_ms_est"]
            host_ms = ss["host_overhead_ms"]
            util = ss["roofline_utilization_est"]
        return {
            "hbm_gbps": self._hbm_gbps,
            "roofline_bytes_per_dispatch": self._roofline_bytes(),
            "kv_bytes_moved_per_dispatch": (
                self._kv_bytes_moved_per_dispatch()
            ),
            "roofline_ms_per_dispatch": round(roof_ms, 4),
            "device_time_ms_per_dispatch": per,
            "host_overhead_ms_per_dispatch": host_ms,
            "roofline_utilization": util,
            "source": (
                "capture" if cap is not None
                else "estimate" if ss is not None else None
            ),
            "captures": self._stats["profile_captures"],
            "steady_state": ss,
            "last_capture": cap,
        }

    # -------------------------------------------------- bytes accounting


    def _kv_live_bytes(self) -> int:
        """Paged: bytes of the live page MAPPINGS — the KV working set
        a fused forward actually reads through the tables, counted per
        slot-table entry rather than per physical page: a COW-shared
        prefix page is DMA'd once per slot that maps it (each row's
        table-driven block fetch is independent), and registry-only
        pinned pages (no slot row maps them) cost a forward nothing.
        Scrape/stats-time only; the mirror may be mid-mutation under
        an HTTP-thread read — a torn count is acceptable monitoring,
        same contract as ``_stats``."""
        from mlcomp_tpu.kvpool import RESERVED_PAGES

        rows = self._pool.tables[: len(self._host)]
        return int((rows >= RESERVED_PAGES).sum()) * (
            self._layout.page_bytes()
        )

    def _roofline_bytes(self) -> int:
        """HBM bytes one dispatch MUST move: weights once per forward
        plus the KV working set (dense buffer, or live pages under the
        paged layout — the honest denominator the roofline satellite
        fixed: charging the full buffer overstated paged bytes)."""
        kv = (
            self._kv_live_bytes() if self._pool is not None
            else self._kv_dense_bytes
        )
        return self.steps_per_dispatch * (self._w_bytes + kv)

    def _roofline_ms(self) -> float:
        return self._roofline_bytes() / (self._hbm_gbps * 1e9) * 1e3

    def _kv_bytes_moved_per_dispatch(self) -> int:
        """Estimated KV bytes one dispatch moves through HBM — the
        cost model behind ``mlcomp_engine_kv_bytes_moved_per_dispatch``.
        Dense: K forwards read the buffer.  Paged FUSED: K forwards read the live pages (the
        whole point of the fused path — per-token appends are noise).
        Paged LAX sandwich: the gather reads the live pages and writes
        the dense view, the core reads it K times, the scatter reads
        it back and rewrites the pages — the round trip the fused path
        deletes."""
        fw = self.steps_per_dispatch
        if self._pool is None:
            return fw * self._kv_dense_bytes
        live = self._kv_live_bytes()
        dense = self._layout.dense_view_bytes(len(self._host))
        if self._paged_attn != "lax":
            if self._kv_fused_kernels:
                return fw * live
            # per-layer gather FALLBACK (non-quant family, kernel-
            # ineligible geometry): each forward still reads the live
            # pages and round-trips a transient dense view through the
            # attention consumer — not the kernels' page-streaming win
            return fw * (live + 2 * dense)
        return (fw + 2) * dense + 2 * live

    def _complete_admission(self) -> None:  # graftcheck: runs-on(loop)
        """Final admission boundary: queue the prefix-cache capture
        and insert the prefilled row at a free slot (or export it /
        import its pages).  The plain insert is ENQUEUED, not waited
        for: its operands (the carry, ``adm.cache``, ``adm.last_logits``)
        are the outputs of whatever is still in flight, so the device
        runs it behind the admission's last fused dispatch and ahead of
        the next one while the loop goes on — nothing here reads a
        dispatch back.  The slot comes from the host view, which only
        UNDER-reports free slots (the host learns of a finish a
        boundary late); the row is inactive in every dispatch issued
        before the insert, and ``_Slot.since_seq`` keeps a predecessor's
        tokens in those dispatches from being booked to it.  Only the
        import and the export are called on a drained pipeline (the
        caller's choice); a drain never happens IN this method, so a
        decode-dispatch failure stays engine-scoped, not blamed on the
        joiner.  The admission's final logits are the last REAL
        token's (left-padding puts the prompt tail at the bucket
        end)."""
        adm = self._adm
        jnp = self._jnp
        req = adm.req
        s_bucket = adm.s_bucket
        decoding = any(s is not None for s in self._host)
        exported = adm.handoff is None and self.prefill_only
        t0 = time.perf_counter()
        self._busy_since = t0
        try:
            if adm.handoff is not None:
                self._insert_import(jnp, adm, req, s_bucket)
            elif self.prefill_only:
                self._export_admission(adm)
            else:
                self._insert_admission(jnp, adm, req, s_bucket)
        finally:
            self._busy_since = None
        t1 = time.perf_counter()
        boundaries = self._leave_lane(adm, t1)
        if req.get("rid") and not exported:
            # the row is on the device carry: admit -> inserted is how
            # long this request held the engine's one admission lane
            # (``of`` less ``chunks`` is what all-pad chunks and a
            # prefix hit skipped)
            self.recorder.async_instant(
                "inserted", req["rid"], cat="req", chunks=adm.chunks_run,
                fused_chunks=adm.fused_chunks, boundaries=boundaries,
                of=adm.n_chunks,
            )
        if decoding:
            adm.stall_ms += (t1 - t0) * 1e3
        self._hist_stall.observe(adm.stall_ms)
        if adm.fused_chunks:
            self._stats["admissions_overlapped"] += 1
        if self._inflight:
            self._pstats["inserts_behind_dispatch"] += 1
        self._stats["prefills"] += 1
        self._adm = None

    def _insert_admission(self, jnp, adm, req, s_bucket) -> None:  # graftcheck: runs-on(loop)
        if (self.prefix_cache is not None and not req.get("warmup")
                and not adm.skip_capture):
            # queue the finished prefill's real-token K/V rows for the
            # cache's background worker (the trie dedups: only new
            # suffix rows are stored).  The loop thread pays ONE
            # enqueue — the capture's compile/fetch/copies/insert run
            # off-thread, so the CAPTURE side adds nothing to the
            # admission stall (the hit side's upload is the remaining
            # on-thread cost — see _start_admission).  Safe to hand
            # off: adm.cache is an immutable device pytree the insert
            # below does not donate, and the worker's reference keeps
            # it alive.
            try:
                self.prefix_cache.bind_layout(adm.cache)
                self.prefix_cache.insert_async(
                    self._capture_fn(adm.capture_lo, s_bucket), adm.cache,
                    req["ids"], s_bucket - len(req["ids"]),
                    adm.capture_lo,
                )
            except Exception:
                # capture is best-effort: a fault here degrades the
                # cache, never the request that just finished prefilling
                self._stats["cache_degraded"] += 1
        slot = self._host.index(None)
        row_presence = np.zeros((1, self.vocab), bool)
        if req["repetition_penalty"] != 1.0:
            row_presence[0, np.asarray(req["ids"])] = True
        packed = np.asarray([
            slot, s_bucket, len(req["ids"]), s_bucket - len(req["ids"]),
            req["n_new"], req["eos_id"], req["temperature"], req["top_k"],
            req["top_p"], req["repetition_penalty"],
            # per-request sampling-stream seed: the rid wrapped to
            # stay exact through the f32 packed row (2^23 < 2^24).
            # Uniqueness is only needed among CONCURRENTLY ACTIVE
            # sampled requests — two live rows 8.4M rids apart cannot
            # coexist in a bounded slot pool, so the wrap never
            # collides live streams; warmup rows are greedy and never
            # read it.
            req.get("rid", 0) % (1 << 23),
        ], np.float32)
        extra = ()
        prow = None
        if self._pool is not None:
            # PAGED: compose the slot's table row host-side — NULL for
            # pad/beyond-budget pages, SHARED entries from the registry
            # lease (ref-count bump, zero copy), private allocations
            # for everything the slot writes, with a COW fork where the
            # write span crosses the shared boundary.  All-or-nothing:
            # a NoFreePages here (the admission gate reserved nothing —
            # only one admission runs at a time, and retirements only
            # ADD pages after the gate passed, so this is a true edge)
            # fails the joiner, never leaks.
            from mlcomp_tpu.kvpool import GRAVE_PAGE, NoFreePages

            pool = self._pool
            start_pad, span_end = self._slot_span(
                s_bucket, len(req["ids"]), req["n_new"]
            )
            # LAZY decode allocation: back only the prefill content
            # plus one dispatch of lookahead now; later decode pages
            # allocate as the cursor approaches them
            # (_lazy_extend_tick) — the admission gate budgeted this
            # same alloc_end (_pages_initial)
            alloc_end = self._alloc_end(s_bucket, span_end)
            try:
                prow, pmask, _forks = pool.build_slot_row(
                    start_pad, span_end, shared=adm.page_lease,
                    alloc_end=alloc_end,
                )
            except NoFreePages:
                # genuinely short of PRIVATE pages (shared mappings
                # cost none, so reclaiming on the worst case up front
                # would evict the registry — this feature's own fast
                # path — even when sharing covers the gap): evict LRU
                # registry pins down to the PRIVATE shortfall only and
                # retry once; a second failure is the admission-scoped
                # error the docstring promises
                pool.reclaim(pool.private_pages_needed(
                    start_pad, span_end, shared=adm.page_lease,
                    alloc_end=alloc_end,
                ))
                prow, pmask, _forks = pool.build_slot_row(
                    start_pad, span_end, shared=adm.page_lease,
                    alloc_end=alloc_end,
                )
            wsel = np.where(pmask, prow, GRAVE_PAGE).astype(np.int32)
            extra = (self._dev(prow), self._dev(wsel))
        try:
            with self.recorder.span(
                "insert", track="engine.loop", slot=slot,
                rid=req.get("rid", 0), trace_id=req.get("trace_id"),
            ):
                self._dstate = self._insert_fn()(
                    self._dstate, adm.cache, adm.last_logits,
                    self._dev(row_presence), self._dev(packed), *extra,
                )
        except Exception:
            if prow is not None:
                self._pool.release_row(prow)
            raise
        if self._pool is not None:
            try:
                self._pool.commit_slot_row(slot, prow)
                if not req.get("warmup"):
                    # pin the fresh prompt-prefix pages under the
                    # placement key so the NEXT same-placement shared
                    # prefix maps them with no prefill at all
                    self._pool.registry_register(
                        s_bucket, s_bucket - len(req["ids"]), req["ids"],
                        prow,
                    )
            finally:
                if adm.page_lease is not None:
                    adm.page_lease.release()
                    adm.page_lease = None
        sl = _Slot(
            req,
            cursor=s_bucket,
            position=len(req["ids"]),
            start=s_bucket - len(req["ids"]),
            remaining=req["n_new"],
            since_seq=self._inflight[-1][2] + 1 if self._inflight else 0,
        )
        if self._pool is not None:
            # lazy-allocation bookkeeping: the committed row covers
            # page-aligned slots up to ceil(alloc_end / T) * T
            start_pad, span_end = self._slot_span(
                s_bucket, len(req["ids"]), req["n_new"]
            )
            T = self._pool.page_tokens
            sl.span_end = span_end
            sl.alloc_upto = -(-self._alloc_end(s_bucket, span_end)
                              // T) * T
        self._host[slot] = sl

    # --------------------------------------------- disaggregated handoff

    def _export_admission(self, adm) -> None:  # graftcheck: runs-on(loop)
        """Prefill-only completion: capture the finished prompt's KV
        rows (the prefix cache's device->host capture programs, chunk-
        aligned), tile them into page payloads, and resolve the
        request's future with the serialized handoff — the prompt is
        now a transferable object a decode replica imports with
        :meth:`import_pages`.  Faults here are admission-scoped (the
        caller's except fails only this request); the
        ``engine.export`` chaos point models a replica dying
        mid-transfer."""
        from mlcomp_tpu.kvpool.transfer import (
            encode_handoff,
            rows_to_page_tiles,
        )

        req = adm.req
        ids = req["ids"]
        s_bucket = adm.s_bucket
        T = self._export_T
        start_pad = s_bucket - len(ids)
        if (self.prefix_cache is not None and not req.get("warmup")
                and not adm.skip_capture):
            # same best-effort capture enqueue as the insert path: a
            # prefill replica is WHERE the prefix cache earns its RAM
            # (every request is an admission), so the finished rows
            # feed the trie exactly as a monolithic prefill's would
            try:
                self.prefix_cache.bind_layout(adm.cache)
                self.prefix_cache.insert_async(
                    self._capture_fn(adm.capture_lo, s_bucket),
                    adm.cache, ids, start_pad, adm.capture_lo,
                )
            except Exception:
                self._stats["cache_degraded"] += 1
        lo_page = (start_pad // T) * T
        c = adm.chunk
        lo_chunk = (start_pad // c) * c  # the warm capture programs
        # are chunk-keyed; rows below lo_page are sliced off host-side
        rid = req.get("rid", 0)
        _inject_fault("engine.export")
        with self.recorder.span(
            "handoff_export", track="engine.loop", rid=rid,
            trace_id=req.get("trace_id"), prompt=len(ids),
        ) as sp:
            rows = self._capture_fn(lo_chunk, s_bucket)(adm.cache)
            off = lo_page - lo_chunk
            payloads = []
            for (keystr, axis, _shape, _dt), r in zip(
                self._export_leaves, rows
            ):
                a = np.asarray(r)
                idx = [slice(None)] * a.ndim
                idx[axis] = slice(off, s_bucket - lo_chunk)
                payloads.append(
                    rows_to_page_tiles(a[tuple(idx)], axis, T)
                )
            logits = np.asarray(adm.last_logits, np.float32)
            meta = {
                "s_bucket": s_bucket, "start_pad": start_pad,
                "page_tokens": T,
                "n_pages": (s_bucket - lo_page) // T,
                "ids": [int(t) for t in ids],
                "n_new": int(req["n_new"]),
                # the per-request sampling-stream seed: carried so a
                # SAMPLED request's tokens stay reproducible on a
                # decode engine built with the same seed (greedy never
                # reads it) — same wrap as the local insert's packed row
                "rseed": rid % (1 << 23),
                "trace_id": req.get("trace_id"),
                "req": {
                    "temperature": req["temperature"],
                    "top_k": req["top_k"], "top_p": req["top_p"],
                    "eos_id": req["eos_id"],
                    "logprobs": req["logprobs"],
                    "repetition_penalty": req["repetition_penalty"],
                },
                "leaves": [
                    {"key": keystr}
                    for keystr, _ax, _sh, _dt in self._export_leaves
                ],
            }
            blob = encode_handoff(meta, logits, payloads)
            sp["pages"] = meta["n_pages"]
            sp["bytes"] = len(blob)
        if not req.get("warmup"):
            self._stats["handoffs_exported"] += 1
            self._stats["kv_pages_exported"] += meta["n_pages"]
            self._stats["handoff_bytes_exported"] += len(blob)
        now = time.perf_counter()
        if not req.get("warmup"):
            # the handoff wall IS this request's service time on the
            # prefill replica: feed the TTFT reservoir so the replica's
            # latency percentiles (and SLOs) mean prefill latency
            ttft_ms = (now - req["t_submit"]) * 1e3
            self._lat_ttft.append(ttft_ms)
            self._lat_ttft_n += 1
            self._hist_ttft.observe(ttft_ms)
        if rid:
            self._cancelled.discard(rid)
            self.recorder.async_end(
                "request", rid, cat="req", exported=True,
            )
        _set_result(req["future"], {
            "handoff": blob,
            "prefill_tokens": len(ids),
            "pages": meta["n_pages"],
            "cache_hit_tokens": int(req.get("cache_hit_tokens", 0)),
            "latency_ms": round((now - req["t_submit"]) * 1e3, 2),
            "trace_id": req.get("trace_id"),
        })

    def _import_write_fn(self, n_pages: int):
        """Write one handoff's payload tiles into the page arrays at
        ``page_ids`` — the device half of :meth:`import_pages`.  One
        program per distinct prompt-page count (bounded by pages per
        bucket); composes on the donated carry after the insert."""
        key = ("import_write", n_pages)
        if key not in self._fns:
            def write(dstate, page_ids, *payload):
                out = dict(dstate)
                out["pages"] = [
                    pg.at[page_ids].set(pl)
                    for pg, pl in zip(dstate["pages"], payload)
                ]
                return self._constrain_carry(out)

            self._fns[key] = self._jax.jit(write, donate_argnums=(0,))
        return self._fns[key]

    def _insert_import(self, jnp, adm, req, s_bucket) -> None:  # graftcheck: runs-on(loop)
        """Insert an IMPORTED prefill at a free slot: allocate the
        slot's pages (prompt span + one dispatch of decode lookahead,
        the same lazy-allocation currency a local insert uses), zero
        the decode-span pages through the regular insert program, then
        write the payload tiles into the prompt pages and register
        them under the placement key — the next same-placement shared
        prefix maps the IMPORTED pages copy-on-write, exactly as if
        this replica had prefilled them itself.  A dry pool here is
        the admission-scoped typed failure (``NoFreePages``), with the
        same reclaim-then-retry the local insert runs; nothing leaks
        on any failure path (the uncommitted row is released)."""
        from mlcomp_tpu.kvpool import GRAVE_PAGE, NoFreePages

        hd = adm.handoff
        meta = hd["meta"]
        pool = self._pool
        T = pool.page_tokens
        ids = req["ids"]
        slot = self._host.index(None)
        start_pad, span_end = self._slot_span(
            s_bucket, len(ids), req["n_new"]
        )
        alloc_end = self._alloc_end(s_bucket, span_end)
        try:
            prow, pmask, _forks = pool.build_slot_row(
                start_pad, span_end, alloc_end=alloc_end,
            )
        except NoFreePages:
            pool.reclaim(pool.private_pages_needed(
                start_pad, span_end, alloc_end=alloc_end,
            ))
            prow, pmask, _forks = pool.build_slot_row(
                start_pad, span_end, alloc_end=alloc_end,
            )
        p0, p_n = start_pad // T, s_bucket // T
        # write routing: decode-span private pages zero-fill from the
        # fresh (all-zero) admission cache — a recycled page must not
        # leak a previous stream's bytes into the masked-but-readable
        # span — while the prompt pages route to the graveyard here
        # (the payload write below is what fills them)
        wsel = np.where(pmask, prow, GRAVE_PAGE).astype(np.int32)
        wsel[p0:p_n] = GRAVE_PAGE
        row_presence = np.zeros((1, self.vocab), bool)
        if req["repetition_penalty"] != 1.0:
            row_presence[0, np.asarray(ids)] = True
        packed = np.asarray([
            slot, s_bucket, len(ids), start_pad,
            req["n_new"], req["eos_id"], req["temperature"],
            req["top_k"], req["top_p"], req["repetition_penalty"],
            # the PREFILL side's sampling-stream seed, not a local
            # rid: sampled tokens must not depend on which replica
            # admitted the prompt
            int(meta.get("rseed", 0)) % (1 << 23),
        ], np.float32)
        extra = (self._dev(prow), self._dev(wsel))
        n_pages = p_n - p0
        try:
            with self.recorder.span(
                "import", track="engine.loop", slot=slot,
                rid=req.get("rid", 0), pages=n_pages,
                trace_id=req.get("trace_id"),
            ):
                zeros = self._prefill_init_fn()(self._dev(0, np.int32))
                self._dstate = self._insert_fn()(
                    self._dstate, zeros,
                    self._dev(hd["logits"], np.float32),
                    self._dev(row_presence), self._dev(packed), *extra,
                )
                self._dstate = self._import_write_fn(n_pages)(
                    self._dstate,
                    self._dev(prow[p0:p_n], np.int32),
                    *[self._dev(p) for p in hd["payloads"]],
                )
        except Exception:
            pool.release_row(prow)
            raise
        try:
            pool.commit_slot_row(slot, prow)
            if not req.get("warmup"):
                pool.registry_register(s_bucket, start_pad, ids, prow)
        finally:
            adm.handoff = None  # drop the payload buffers
        sl = _Slot(
            req,
            cursor=s_bucket,
            position=len(ids),
            start=start_pad,
            remaining=req["n_new"],
        )
        sl.span_end = span_end
        sl.alloc_upto = -(-alloc_end // T) * T
        self._host[slot] = sl
        self._stats["handoffs_imported"] += 1
        self._stats["kv_pages_imported"] += n_pages
        self._stats["handoff_bytes_imported"] += int(hd.get("bytes", 0))

    def _finish(self, slot_idx: int, error: Optional[Exception] = None):  # graftcheck: runs-on(loop)
        sl = self._host[slot_idx]
        self._host[slot_idx] = None
        if sl is None:
            return
        req = sl.req
        if req.get("rid"):
            self._cancelled.discard(req["rid"])
        if req["stream"] is not None:
            req["stream"].put(None)
        if error is not None:
            if req.get("rid"):
                self.recorder.async_end(
                    "request", req["rid"], cat="req", error=True,
                )
            _fail_future(req["future"], error)
            return
        now = time.perf_counter()
        if req.get("rid"):
            self.recorder.async_end(
                "request", req["rid"], cat="req",
                tokens=len(sl.emitted),
            )
        if sl.t_first is not None and not req.get("warmup"):
            # latency reservoirs behind the stats() percentiles: TTFT
            # is submit -> first token at the HOST (includes queueing,
            # admission, and any pipeline lag — what a client sees);
            # per-token is the mean decode interval after it (needs a
            # second token to exist)
            ttft_ms = (sl.t_first - req["t_submit"]) * 1e3
            self._lat_ttft.append(ttft_ms)
            self._lat_ttft_n += 1
            self._hist_ttft.observe(ttft_ms)
            n = len(sl.emitted)
            if n > 1:
                tok_ms = (now - sl.t_first) * 1e3 / (n - 1)
                self._lat_tok.append(tok_ms)
                self._lat_tok_n += 1
                self._hist_tok.observe(tok_ms)
        result = {
            "ids": [t for t, _ in sl.emitted],
            "latency_ms": round((now - req["t_submit"]) * 1e3, 2),
            "batched_with": self.slots,
            # echo the request's trace id: the client can hand it to
            # GET /trace?trace_id= (or the fleet merger) to pull
            # exactly this request's spans
            "trace_id": req.get("trace_id"),
        }
        if self.prefix_cache is not None:
            # per-request accounting: prompt tokens whose prefill the
            # cache actually skipped (chunk-aligned, 0 on a miss)
            result["cache_hit_tokens"] = int(req.get("cache_hit_tokens", 0))
        if req["logprobs"]:
            result["logprobs"] = [round(lp, 5) for _, lp in sl.emitted]
        # idempotent: the watchdog may have failed this future during a
        # stall the runtime later recovered from — its verdict stands
        _set_result(req["future"], result)

    def _issue_dispatch(self, fused=None,
                        t_open: Optional[float] = None) -> float:  # graftcheck: runs-on(loop)
        """Issue ONE dispatch and return WITHOUT blocking on its
        outputs: one device call (state device-carried + donated),
        nothing per-slot uploaded.  The donated carry chains device-
        side — dispatch N+1's inputs are dispatch N's still-in-flight
        outputs, which JAX sequences on the device stream — and the
        packed token buffer joins ``_inflight`` for ``_process_oldest``
        to resolve a boundary later.  That gap is the overlap: the
        host's dispatch+unpack work for N runs while the device
        executes N+1.

        ``fused`` (an ``(adm, chunk, positions)`` triple from
        ``_prep_fused_chunk``) makes this a FUSED dispatch: the same
        program also runs one prefill chunk against the admission's
        carried cache, advancing the admission without a dedicated
        dispatch — the decode stream never pauses for it.

        The whole call is the ``issue`` span (lazy page growth, the
        device call, the in-flight bookkeeping), opened at ``t_open``
        — the stamp that closed the loop's previous span — and its
        closing stamp is returned for the next one."""
        if t_open is None:
            t_open = time.perf_counter()
        seq = next(self._dispatch_seq)
        try:
            self._issue(seq, fused)
        finally:
            t_close = self._loop_span(
                "issue", t_open, seq=seq, fused=fused is not None,
            )
        return t_close

    def _issue(self, seq: int, fused) -> None:  # graftcheck: runs-on(loop)
        # lazy decode-page growth BEFORE the issue: the dispatch about
        # to go out (plus everything already in flight) must find every
        # cache slot it can write backed by a page
        self._lazy_extend_tick()
        self._busy_since = time.perf_counter()
        try:
            # chaos surface: raise = dispatch exception (the loop fails
            # everything and dies cleanly), sleep = wedged runtime (the
            # watchdog's stall clock is already running)
            _inject_fault("engine.dispatch")
            if fused is not None:
                adm, chunk, positions = fused
                # dispatch-lifetime async span opens BEFORE the call so
                # the fused chunk's span nests inside it in the trace
                self.recorder.async_begin(
                    "dispatch", seq, cat="disp",
                    inflight=len(self._inflight) + 1, fused=True,
                )
                with self.recorder.span(
                    "prefill_chunk", track="engine.loop",
                    chunk=adm.next_chunk, of=adm.n_chunks,
                    rid=adm.req.get("rid", 0), fused=True, seq=seq,
                    trace_id=adm.req.get("trace_id"),
                ):
                    (self._dstate, packed, logits,
                     adm.cache) = self._fused_dispatch_fn(adm.chunk)(
                        self.variables, self._dstate, adm.cache,
                        chunk, positions, adm.kv_mask,
                    )
                adm.last_logits = logits
                adm.next_chunk += 1
                adm.chunks_run += 1
                adm.fused_chunks += 1
                self._stats["prefill_chunks"] += 1
                self._stats["fused_chunks"] += 1
            else:
                self._dstate, packed = self._dispatch_fn()(
                    self.variables, self._dstate
                )
        finally:
            self._busy_since = None
        pr = self._profile
        if pr is not None and pr["profiler"].active:
            # capture-window accounting: which dispatch family this
            # window's device time belongs to
            fam = self._family_name(
                fused[0].chunk if fused is not None else None
            )
            pr["families"][fam] = pr["families"].get(fam, 0) + 1
        # carry the dispatch's OWN step depth: adaptive K can change
        # between issues, and the lazy page allocator's lookahead must
        # price the in-flight window by what each dispatch will
        # actually advance, not by the current knob
        t_issued = time.perf_counter()
        self._account(t_issued)  # before _inflight grows: see _account
        self._inflight.append((packed, t_issued, seq, self.steps_per_dispatch))
        p = self._pstats
        p["issued"] += 1
        p["inflight_sum"] += len(self._inflight)
        # rows whose window the attention walks in this dispatch, by
        # the host's slot mirror (a row that retires inside an
        # in-flight dispatch still counts until its tokens are read)
        ctx = [sl.position for sl in self._host if sl is not None]
        p["rows_attended"] += len(ctx)
        p["rows_total"] += len(self._host)
        # of the empty rows, those a request is waiting for: everyone
        # queued and the one mid-prefill in the lane, whose row goes
        # out empty once more; the rest nobody asked for
        p["rows_starved"] += min(
            len(self._host) - len(ctx),
            len(self._pending) + (self._adm is not None),
        )
        p["kv_rows_written"] += len(ctx) * self.steps_per_dispatch
        # tokens of context the live rows hold, over the layers, and
        # the part of them a layer's window lets its attention read
        windowed = [w for w in self._attn_windows if w is not None]
        live_w = sum(ctx) * len(windowed)
        seen_w = sum(min(c, w) for w in windowed for c in ctx)
        full = sum(ctx) * (len(self._attn_windows) - len(windowed))
        p["kv_live"] += full + live_w
        p["kv_attended"] += full + seen_w
        p["kv_live_window"] += live_w
        p["kv_attended_window"] += seen_w
        if self._kv_walk is not None and ctx:
            # what the walk moves for those windows in the dispatch's
            # first step: [start, cursor], raised to a layer's window
            from mlcomp_tpu.ops.pallas.decode_attention import (
                kv_walk_counts,
            )

            lo, hi = (np.array(x) for x in zip(*(
                (sl.start, sl.cursor + 1)
                for sl in self._host if sl is not None
            )))
            for w, layers in self._attn_window_layers.items():
                tokens, trips = kv_walk_counts(
                    lo if w is None else np.maximum(lo, hi - w), hi,
                    *self._kv_walk,
                )
                p["kv_fetched"] += layers * int(tokens.sum())
                p["kv_trips"] += layers * int(trips.sum())
        if len(self._inflight) > p["peak_inflight"]:
            p["peak_inflight"] = len(self._inflight)
        # the dispatch's LIFETIME (issue -> outputs read) as an async
        # span: overlapping spans stack in Perfetto, so depth 2 shows
        # dispatch N+1's span (and its issue) nested inside dispatch
        # N's — overlap_efficiency, drawn
        if fused is None:
            self.recorder.async_begin(
                "dispatch", seq, cat="disp", inflight=len(self._inflight),
            )

    def _process_oldest(self,
                        t_open: Optional[float] = None) -> float:  # graftcheck: runs-on(loop)
        """Block on the OLDEST in-flight dispatch's packed outputs and
        run the host half: stream/bookkeep its tokens, retire finished
        rows.  FIFO processing keeps step numbering, stream order, and
        slot retirement identical to the synchronous loop at any
        pipeline depth.

        Two spans share the stamp between them: ``resolve`` (opened at
        ``t_open``, like ``issue``) is the blocked fetch, ``unpack``
        the host half; unpack's closing stamp is returned."""
        if t_open is None:
            t_open = time.perf_counter()
        self._account(t_open)  # before _inflight shrinks: see _account
        packed, _t_issued, seq, _steps = self._inflight.popleft()
        self._busy_since = t_open
        try:
            _inject_fault("engine.resolve")  # chaos: slow readback
            arr = np.asarray(packed)  # (3, K, slots) f32, 1 transfer
            if arr.ndim == 1:
                # a model whose layers sow counts: they ride the tail
                # of the same buffer (_pack_counts)
                n = len(self._counts)
                self._counts += arr[-n:]
                arr = arr[:-n].reshape(3, -1, len(self._host))
            while self._counts_pending:
                self._counts += np.asarray(self._counts_pending.popleft())
        finally:
            self._busy_since = None
            t_done = self._loop_span("resolve", t_open, seq=seq)
            self._account(t_done, "wait_ms")
        prc = self._profile
        if prc is not None and prc["profiler"].active:
            # the np.asarray above fetched the dispatch's packed
            # outputs to the host: the device finished this dispatch NOW, so this
            # stamp — not the later _profile_tick, which runs after
            # boundary maintenance may have blocked in the idle queue
            # pump — is where the capture window's wall ends
            prc["t_last"] = t_done
        self.recorder.async_end("dispatch", seq, cat="disp")
        toks = arr[0].astype(np.int32)
        lps = arr[1]
        valid = arr[2] > 0.5
        n_tokens = int(valid.sum())
        self._stats["dispatches"] += 1
        # "steps" counts device FORWARDS: the K of the scan
        self._stats["steps"] += toks.shape[0]
        self._stats["emitted_tokens"] += n_tokens
        for kk in range(toks.shape[0]):
            self.step_count += 1
            for i, sl in enumerate(self._host):
                if sl is None or not valid[kk, i] or seq < sl.since_seq:
                    continue
                tok, lp = int(toks[kk, i]), float(lps[kk, i])
                if sl.t_first is None:
                    sl.t_first = t_done
                    if sl.req.get("rid"):
                        self.recorder.async_instant(
                            "first_token", sl.req["rid"], cat="req",
                        )
                sl.emitted.append((tok, lp))
                if sl.req["stream"] is not None:
                    sl.req["stream"].put({
                        "token": tok, "logprob": round(lp, 5),
                        "step": self.step_count,
                    })
                sl.cursor += 1
                sl.position += 1
                sl.remaining -= 1
                if sl.remaining <= 0 or tok == sl.req["eos_id"]:
                    self._finish(i)
                    self._release_slot_pages(i)
        return self._loop_span("unpack", t_done, seq=seq, tokens=n_tokens)

    def _run_dispatch(self) -> None:  # graftcheck: runs-on(loop)
        # the synchronous compose (= pipeline depth 1): issue, then
        # resolve everything in flight.  Kept as the one-call entry
        # point for the tests and tools that drive the engine by hand.
        self._issue_dispatch()
        while self._inflight:
            self._process_oldest()

    def _loop(self) -> None:  # graftcheck: runs-on(loop)
        try:
            self._loop_body()
        finally:
            if self._dist is not None and self._dist.is_coordinator:
                # whatever killed the coordinator's loop, the gang must
                # not wedge in recv: broadcast the stop record (best
                # effort — a dead channel means followers see it closed)
                try:
                    self._dist.send({"stop": True, "new": [],
                                     "ctrl": [], "retired": [],
                                     "k": self.steps_per_dispatch})
                except Exception:
                    pass
            # LOOP-OWNED final drain: whatever path ended the loop —
            # close(), a fatal error, a watchdog stall verdict, or a
            # wedged dispatch finally returning after an abandoned
            # close() — nothing may be left waiting on a future this
            # thread will never resolve.  Idempotent vs close()'s own
            # drain (_finish clears the slot, _fail_future tolerates
            # the loser of the race).
            err = self._broken or RuntimeError("decode engine closed")
            # unread in-flight outputs are dropped, not resolved: their
            # rows' futures fail below, and blocking here on a possibly
            # wedged device would stall close()'s join
            self._inflight.clear()
            # an armed/active capture dies with the loop: close the
            # trace window, fail its future — never a dangling session
            self._finish_profile(error=err)
            for i in range(len(self._host)):
                self._finish(i, error=err)
            self._fail_admission(err)
            self._drain_pending(err)
            self._drain_queue(err)

    # ------------------------------------------------ boundary maintenance

    def _pump_queue(self, block_s: float = 0.0):  # graftcheck: runs-on(loop)
        """Move everything parked in the thread-safe submit queue into
        the loop-owned ``_pending`` deque, where the deadline/cancel
        sweep can retire QUEUED requests at a dispatch boundary instead
        of only when a slot frees.  Blocks up to ``block_s`` for the
        first item when the engine is idle.  Returns ``(new, ctrls)``
        — the requests pumped THIS boundary and any control items
        (``warm_on_loop``) — so a distributed coordinator can
        broadcast exactly what entered the loop at this boundary."""
        new: List[Dict[str, Any]] = []
        ctrls: List[Dict[str, Any]] = []
        try:
            if block_s:
                with self._idle_wait():
                    item = self._queue.get(timeout=block_s)
            else:
                item = self._queue.get_nowait()
            while True:
                # skip poison pills and futures submit's close/broken
                # race check already failed (their request must not be
                # decoded by a restarted loop)
                if item is not _POISON and "ctrl" in item:
                    ctrls.append(item)
                elif item is not _POISON and not item["future"].done():
                    self._park(item)
                    new.append(item)
                item = self._queue.get_nowait()
        except queue.Empty:
            pass
        return new, ctrls

    def _park(self, req: Dict[str, Any]) -> None:  # graftcheck: runs-on(loop)
        """``req`` joins the FIFO behind the lane.  Everyone queued
        waits for what the head waits for, so one set of cumulative
        blocked_*_ms serves them all: the request keeps the sums as it
        found them, with the stamp they are booked up to, and
        ``_take_lane`` subtracts."""
        p = self._pstats
        req["t_parked"] = self._t_lane
        req["blocked0"] = (p["blocked_lane_ms"], p["blocked_slot_ms"],
                           p["blocked_pages_ms"])
        self._pending.append(req)

    def _retire_check(
        self, req: Dict[str, Any], now: Optional[float] = None,
    ) -> Optional[Exception]:
        """The retirement verdict for one request: RequestCancelled /
        DeadlineExceeded when due, else None."""
        rid = req.get("rid")
        if rid and rid in self._cancelled:
            return RequestCancelled(f"request {rid} cancelled")
        td = req.get("t_deadline")
        if td is not None:
            if now is None:
                now = time.perf_counter()
            if now >= td:
                return DeadlineExceeded(
                    f"request {rid or '?'} exceeded its deadline"
                )
        return None

    def _count_retire(self, err: Exception, req: Dict[str, Any]) -> None:  # graftcheck: runs-on(loop)
        rid = req.get("rid", 0)
        if isinstance(err, RequestCancelled):
            self._stats["cancelled"] += 1
            self.recorder.instant("cancel", track="engine.loop", rid=rid)
        else:
            self._stats["deadline_exceeded"] += 1
            self.recorder.instant("deadline", track="engine.loop", rid=rid)
        self._cancelled.discard(rid)

    def _boundary_maintenance(self, block_s: float = 0.0,
                              include_adm: bool = False):  # graftcheck: runs-on(loop)
        """Per-boundary housekeeping (loop thread): pump the submit
        queue, then retire queued and active requests whose deadline
        passed or whose rid was cancelled.  Queued requests fail in
        place (no slot was ever taken); an active row is deactivated on
        DEVICE (the engine's own retirement path only fires at EOS/
        budget) and its slot freed for the next admission.  Fault-free
        cost is one queue poll + an O(slots + pending) scan per
        boundary (not measured on the chip).

        Returns ``(new, ctrls, retired)``: the requests/ctrl items
        pumped this boundary and the ``(rid, status)`` retirements it
        performed — a distributed coordinator broadcasts these so
        followers replay the identical device sequence
        (``include_adm`` folds the in-flight admission's verdict into
        the same sweep; in single-host mode the loop body checks the
        admission itself, time-rechecked, so the default stays off)."""
        new, ctrls = self._pump_queue(block_s)
        retired: List[Tuple[int, str]] = []
        if (not self._pending and not self._cancelled
                and (not include_adm or self._adm is None
                     or self._adm.req.get("t_deadline") is None)
                and all(
                    s is None or s.req.get("t_deadline") is None
                    for s in self._host
                )):
            return new, ctrls, retired
        now = time.perf_counter()
        if self._pending:
            kept: Deque[Dict[str, Any]] = deque()
            for req in self._pending:
                err = self._retire_check(req, now)
                if err is None:
                    kept.append(req)
                else:
                    self._count_retire(err, req)
                    self._fail_queued(req, err)
                    retired.append((req.get("rid", 0), err.status))
            self._pending = kept
        for i, sl in enumerate(self._host):
            if sl is None:
                continue
            err = self._retire_check(sl.req, now)
            if err is None:
                continue
            self._count_retire(err, sl.req)
            # device first, then host: once _finish clears the slot a
            # new admission may claim it, and the insert must not race
            # a still-active old row
            self._dstate = self._deactivate_fn()(
                self._dstate, self._dev(i, np.int32)
            )
            self._finish(i, error=err)
            self._release_slot_pages(i)
            retired.append((sl.req.get("rid", 0), err.status))
        if include_adm and self._adm is not None:
            err = self._retire_check(self._adm.req, now)
            if err is not None:
                retired.append((self._adm.req.get("rid", 0), err.status))
                self._count_retire(err, self._adm.req)
                self._fail_admission(err)
        return new, ctrls, retired

    def _adaptive_tick(self) -> None:  # graftcheck: runs-on(loop)
        """Adaptive dispatch depth: one controller decision per
        boundary from the live load signals (queue depth, slot
        occupancy — the same signals the metrics-history ring samples
        as ``mlcomp_engine_queue_depth`` / ``active_slots``).  A
        switch retargets the NEXT issue at the warmed ladder program
        for the new K; nothing drains — in-flight packed buffers carry
        their own step depth and the resolve loop is shape-agnostic,
        so mixed-K windows resolve FIFO like any other.  Tokens are
        K-schedule-invariant by construction (see _fresh_dstate's
        rseed), so the controller moves time, never tokens."""
        ctl = self._k_controller
        if ctl is None:
            return
        depth = self._queue.qsize() + len(self._pending)
        active = sum(1 for s in self._host if s is not None)
        k2 = ctl.decide(depth, active, len(self._host))
        if k2 == self.steps_per_dispatch:
            return
        self.steps_per_dispatch = k2
        self._stats["dispatch_k_changes"] += 1
        self.recorder.instant(
            "dispatch_k_change", track="engine.loop", k=k2,
            queue_depth=depth, active=active,
        )

    # ------------------------------------------------- distributed gang

    _WIRE_KEYS = ("ids", "n_new", "temperature", "top_k", "top_p",
                  "eos_id", "logprobs", "repetition_penalty", "rid",
                  "trace_id", "warmup")

    def _wire_out(self, req: Dict[str, Any]) -> Dict[str, Any]:
        """The JSON-serializable subset of a request the coordinator
        broadcasts: everything the loop's DEVICE sequence depends on.
        Futures, streams, and wall-clock fields stay host-local —
        deadlines are enforced by the coordinator's sweep and arrive
        as explicit retirements."""
        return {k: req[k] for k in self._WIRE_KEYS}

    def _wire_in(self, w: Dict[str, Any]) -> Dict[str, Any]:
        """Reconstruct a broadcast request on a follower: a fresh
        (unread) Future, no stream, no local deadline — the follower's
        tokens are discarded, its DEVICE work is the point."""
        fut: Future = Future()
        fut.rid = w.get("rid", 0)
        fut.trace_id = w.get("trace_id")
        return {
            **{k: w[k] for k in self._WIRE_KEYS},
            "future": fut, "stream": None,
            "t_submit": time.perf_counter(), "t_deadline": None,
        }

    def warm_on_loop(self) -> Future:
        """Distributed warmup: run the warm_* precompiles ON the loop
        thread at a boundary (broadcast as a ctrl record, so followers
        compile the same programs at the same point in the device
        sequence — a main-thread warm call would interleave SPMD
        programs nondeterministically against the gang's loop
        dispatches).  Resolves to the program count."""
        if self._dist is None:
            raise RuntimeError(
                "warm_on_loop is the distributed warmup path; "
                "single-host services call the warm_* fns directly"
            )
        if not self.is_coordinator:
            raise RuntimeError(
                "warm_on_loop runs on the coordinator; followers "
                "replay the broadcast ctrl record"
            )
        fut: Future = Future()
        self._queue.put({"ctrl": "warm", "future": fut})
        if self._stop.is_set() or self._broken is not None:
            # same closed-engine race check as submit(): close() may
            # have drained the queue between the guards above and our
            # put — resolve the future ourselves (idempotent)
            _fail_future(fut, self._broken or RuntimeError(
                "decode engine closed"
            ))
        return fut

    def _run_ctrl(self, kind: str,
                  fut: Optional[Future] = None) -> None:  # graftcheck: runs-on(loop)
        if kind != "warm":
            raise RuntimeError(f"unknown ctrl record {kind!r}")
        self._busy_since = time.perf_counter()  # compiles are busy time
        try:
            n = (self.warm_prefix_fns() + self.warm_dispatch_fns()
                 + self.warm_fused_fns())
        except BaseException as e:
            # the waiter must see the real compile error, not a
            # request-timeout masking it; the loop's own break
            # handling still runs (re-raise)
            if fut is not None:
                _fail_future(fut, e)
            raise
        finally:
            self._busy_since = None
        if fut is not None:
            _set_result(fut, n)

    def _apply_retired(self, retired) -> None:  # graftcheck: runs-on(loop)
        """Follower half of the retirement broadcast: perform exactly
        the coordinator's retirements, in its order — queued requests
        fail in place, active rows deactivate ON DEVICE in the same
        slot order (the carries must stay bit-identical), a retired
        admission tears down mid-prefill."""
        for rid, status in retired:
            rid = int(rid)
            err: Exception = (
                RequestCancelled(f"request {rid} cancelled (broadcast)")
                if status == RequestCancelled.status
                else DeadlineExceeded(
                    f"request {rid} exceeded its deadline (broadcast)"
                )
            )
            hit = None
            for req in self._pending:
                if req.get("rid") == rid:
                    hit = req
                    break
            if hit is not None:
                self._pending.remove(hit)
                self._count_retire(err, hit)
                self._fail_queued(hit, err)
                continue
            adm = self._adm
            if adm is not None and adm.req.get("rid") == rid:
                self._count_retire(err, adm.req)
                self._fail_admission(err)
                continue
            for i, sl in enumerate(self._host):
                if sl is not None and sl.req.get("rid") == rid:
                    self._count_retire(err, sl.req)
                    self._dstate = self._deactivate_fn()(
                        self._dstate, self._dev(i, np.int32)
                    )
                    self._finish(i, error=err)
                    self._release_slot_pages(i)
                    break

    def _sync_boundary(self, idle: bool) -> bool:  # graftcheck: runs-on(loop)
        """ONE gang boundary.  Coordinator: pump + sweep + pick K,
        broadcast the record, run any ctrl items.  Follower: receive
        the record and replay it — enqueue the broadcast requests,
        perform the broadcast retirements, adopt the broadcast K, run
        the ctrl items.  After this returns True both sides run the
        IDENTICAL remaining loop body (admission starts, chunk
        issues, inserts, dispatches are all deterministic functions
        of the shared state), so every process emits the same device
        program sequence.  False = the gang is shutting down."""
        dist = self._dist
        if dist.is_coordinator:
            new, ctrls, retired = self._boundary_maintenance(
                block_s=0.2 if idle else 0.0, include_adm=True,
            )
            self._adaptive_tick()
            dist.send({
                "new": [self._wire_out(r) for r in new],
                "ctrl": [c["ctrl"] for c in ctrls],
                "retired": retired,
                "k": self.steps_per_dispatch,
            })
            for c in ctrls:
                self._run_ctrl(c["ctrl"], c.get("future"))
            return True
        from mlcomp_tpu.parallel.distributed import ChannelClosed

        try:
            if idle:
                with self._idle_wait():
                    rec = dist.recv()
            else:
                rec = dist.recv()
        except ChannelClosed:
            return False
        if rec.get("stop"):
            return False
        for w in rec.get("new", ()):
            self._park(self._wire_in(w))
        self._apply_retired(rec.get("retired", ()))
        k2 = int(rec.get("k", self.steps_per_dispatch))
        if k2 != self.steps_per_dispatch:
            self.steps_per_dispatch = k2
            self._stats["dispatch_k_changes"] += 1
        for kind in rec.get("ctrl", ()):
            self._run_ctrl(kind)
        return True

    # -------------------------------------------------------- drive loop

    def _admission_tick(self) -> bool:  # graftcheck: runs-on(loop)
        """The PREFILL CORE's per-boundary work, extracted from the
        drive loop so it runs with or without a decode fleet: start
        the next admittable request, retire a cancelled/expired
        admission, advance one prefill chunk (fused onto this
        boundary's decode dispatch when rows are decoding, staged
        otherwise), and complete — insert, EXPORT (prefill-only
        engines), or IMPORT (handoff admissions, which are born
        complete).  A ``prefill_only`` engine's loop runs ONLY this:
        with no rows ever active, chunks run staged, nothing fuses,
        and the decode legs of the loop stay inert.  Returns True when
        a fused chunk issued this boundary's dispatch."""
        deferred = False
        if (self._adm is None and None in self._host
                and self._pending):
            # STAGED join drain only: fused admissions start
            # against their own fresh cache, and the host slot
            # view can only UNDER-report free slots, so no
            # drain is needed to begin one.  FINISH boundaries
            # never need a drain either way: the device
            # retires rows itself, so an in-flight dispatch on
            # a finished row emits nothing — the host just
            # learns one boundary later.  The paged layout may
            # DEFER the head (free-page budget) — see
            # _pop_admittable.
            head = self._pending[0]
            req = self._pop_admittable()
            deferred = (req is None and bool(self._pending)
                        and self._pending[0] is head)
            if req is not None:
                if not self.fused_admission:
                    self._drain_inflight()
                try:
                    # the admission's host set-up (padded row, mask
                    # upload, cache lookups, the fresh prefill cache)
                    with self.recorder.span(
                        "admission_start", track="engine.loop",
                        rid=req.get("rid", 0),
                        trace_id=req.get("trace_id"),
                    ):
                        self._start_admission(req)
                except Exception as e:
                    if self._in_lane is not None:
                        # it had taken the lane: close its span
                        self._leave_lane(
                            self._in_lane, time.perf_counter(), error=True)
                    self._fail_queued(req, e)
        if self._pending:
            # the lane had its one chance this boundary, and the
            # queue's head is still queued: no row beyond the one the
            # admission in the lane will take (a free lane would not
            # help), else the lane is another request's, else the page
            # budget deferred the head; _book_boundary books the
            # boundary's length under it.  None of the three: the head
            # only waits for the next tick (the one before it failed)
            free = self._host.count(None) - (self._adm is not None)
            self._head_blocked = (
                "blocked_slot_ms" if free <= 0
                else "blocked_lane_ms" if self._adm is not None
                else "blocked_pages_ms" if deferred else None
            )
        if self._adm is not None and self._dist is None:
            # a cancel/deadline landing mid-prefill retires the
            # admission between its chunks.  Distributed gangs
            # retire ONLY at the broadcast boundary (a local
            # time re-check here would diverge the gang's
            # device sequence)
            err = self._retire_check(self._adm.req)
            if err is not None:
                self._count_retire(err, self._adm.req)
                self._fail_admission(err)
        issued = False
        adm = self._adm
        if adm is not None and adm.next_chunk < adm.n_chunks:
            if self.fused_admission and any(
                s is not None for s in self._host
            ):
                # FUSED: this boundary's dispatch runs the K
                # decode steps AND the admission's next chunk
                # as one donated program.  Host-side prep
                # faults (incl. the engine.fused_prefill chaos
                # point) are admission-scoped: the fleet falls
                # through to a plain dispatch below.
                try:
                    prep = self._prep_fused_chunk(adm)
                except Exception as e:
                    self._fail_admission(e)
                else:
                    self._issue_dispatch(fused=(adm, *prep))
                    issued = True
            else:
                # STAGED chunk on a drained pipeline (the
                # bisect mode — and with no rows decoding
                # there is no dispatch to ride anyway)
                self._drain_inflight()
                try:
                    self._run_admission_chunk()
                except Exception as e:
                    self._fail_admission(e)
        adm = self._adm
        if adm is not None and adm.next_chunk >= adm.n_chunks:
            # all chunks issued, the last possibly still in flight
            # inside the fused dispatch above: the insert is enqueued
            # behind it (see _complete_admission) and the loop goes on
            # to keep pipeline_depth - 1 in flight.  A staged last
            # chunk left the pipeline empty already.  Only an import
            # (born complete, dispatches may be in flight) and an
            # export (fetches to the host anyway) drain first, at LOOP
            # level: a dispatch failure there is the FLEET's error,
            # never the joiner's.  Insert / export / import faults are
            # admission-scoped.
            with self._admission_complete_span(adm):
                if adm.handoff is not None or self.prefill_only:
                    self._drain_inflight()
                try:
                    self._complete_admission()
                except Exception as e:
                    self._fail_admission(e)
        return issued

    def _loop_body(self) -> None:  # graftcheck: runs-on(loop)
        # every iteration is one ``boundary`` span on the engine.loop
        # track, tiled without a gap by maintenance | admission_tick |
        # issue | (resolve unpack)*: each child opens at the stamp that
        # closed the one before (``t``), and the next boundary opens
        # where this one closed (``t0``)
        t0 = self._t_acct = self._t_lane = time.perf_counter()
        while not (self._stop.is_set() or self._exit_loop.is_set()):
            if self._broken is not None:
                # engine-level failure (donated buffers may be gone):
                # fail every waiter and EXIT — the watchdog sees a
                # clean death and decides whether to restart
                return
            try:
                # one admission in flight at a time, one CHUNK of it
                # per boundary.  FUSED (default): the chunk rides the
                # boundary's decode dispatch, chunks compose on the
                # admission's own fresh cache, and the final insert is
                # enqueued behind the last fused dispatch — the
                # pipeline never drains for an admission, and the host
                # prepares the next boundary (maintenance, the next
                # admission's start, the issue) while the device still
                # runs this one.  STAGED (fused_admission=False, and
                # any admission with no decode fleet to ride): the old
                # behavior — drain at the join, every chunk its own
                # dispatch, synchronous boundaries.
                idle = (
                    self._adm is None and not self._inflight
                    and not self._pending
                    and all(s is None for s in self._host)
                )
                if self._dist is not None:
                    # distributed gang: the boundary's admissions,
                    # retirements, and K all flow through the
                    # coordinator's broadcast so every process runs
                    # the identical device sequence
                    if not self._sync_boundary(idle):
                        return
                else:
                    self._boundary_maintenance(
                        block_s=0.2 if idle else 0.0
                    )
                    # adaptive dispatch depth: pick this boundary's K
                    # from the live load signals BEFORE any issue
                    # below (the fused program family is K-keyed too)
                    self._adaptive_tick()
                # on-demand device capture (GET /profile): start/stop
                # the trace window at this boundary when one is armed
                self._profile_tick()
                if self._pool is not None:
                    # elastic slots: grow behind a full pool when the
                    # head request fits the page budget, shrink to the
                    # floor at quiesce
                    self._elastic_tick()
                t = self._loop_span("maintenance", t0)
                issued = self._admission_tick()
                t = self._loop_span("admission_tick", t)
                if not issued and any(s is not None for s in self._host):
                    t = self._issue_dispatch(t_open=t)
                    issued = True
                # steady state keeps pipeline_depth dispatches in
                # flight (resolve down to depth-1 after each issue);
                # staged-admission boundaries run synchronous, and
                # with nothing newly issued whatever remains resolves
                # now — the pipeline never idles on unread outputs
                keep = self.pipeline_depth - 1 if (
                    issued and (self._adm is None or self.fused_admission)
                ) else 0
                while len(self._inflight) > keep:
                    t = self._process_oldest(t_open=t)
                t0 = self._loop_span("boundary", t0, t)
                self._book_boundary(t0)
            except Exception as e:  # engine-level failure
                self._broken = e
                if self._unhealthy_reason is None:
                    self._unhealthy_reason = (
                        f"drive loop error: {type(e).__name__}: {e}"
                    )
                # drop unread in-flight outputs NOW (they'd pin device
                # buffers), fail everything via the finally drain, and
                # die CLEANLY — stranding queued futures on a dead
                # thread was this PR's headline bug, and a clean death
                # is what lets the watchdog restart the loop
                self._inflight.clear()
                return

    # ----------------------------------------------------------- watchdog

    def _watchdog_loop(self) -> None:
        """Monitor thread: declares a stall when the drive loop sits in
        one device call past ``dispatch_stall_timeout`` (fails the
        waiters host-side with ``EngineStalled`` and asks the loop to
        exit when it unsticks), and restarts a provably-DEAD loop —
        once per incident, and only if the engine resolved at least one
        dispatch since the previous restart (a crash loop stays down
        instead of flapping)."""
        stall_declared = False
        while True:
            # timeout re-read every tick: operators/tests may retune
            # it on a live engine (generous during compile-heavy
            # warmup, tight in steady state; None/0 = stall detection
            # off for that tick — dead-loop restarts keep working)
            timeout = self.dispatch_stall_timeout
            wait_s = min(max((timeout or 1.0) / 4.0, 0.02), 1.0)
            if self._stop.wait(wait_s):
                return
            try:
                busy = self._busy_since
                if (timeout and not stall_declared and busy is not None
                        and time.perf_counter() - busy > timeout
                        and self._thread.is_alive()):
                    stall_declared = True
                    self._fire_stall(time.perf_counter() - busy)
                if not self._thread.is_alive() and not self._stop.is_set():
                    if self._maybe_restart():
                        stall_declared = False
            except Exception as e:
                # the watchdog is the backstop: it must survive its own
                # races (e.g. a deque mutating mid-snapshot while the
                # loop unsticks) — a dead watchdog would silently drop
                # stall detection AND the bounded restart
                warnings.warn(
                    f"engine watchdog tick failed ({e!r}); retrying "
                    "next tick",
                )

    def _fire_stall(self, stuck_s: float) -> None:
        err = EngineStalled(
            f"dispatch exceeded dispatch_stall_timeout="
            f"{self.dispatch_stall_timeout}s (stuck {stuck_s:.1f}s)"
        )
        # graftcheck: ignore[unguarded-write] -- watchdog thread; GIL-atomic add to a key only this thread writes
        self._stats["watchdog_stalls"] += 1
        self._unhealthy_reason = str(err)
        self._broken = err      # submits fail fast from here on
        self._exit_loop.set()   # the loop dies when the call returns
        self.recorder.instant(
            "watchdog_fire", track="engine.watchdog",
            stuck_s=round(stuck_s, 3),
        )
        # fail the WAITERS now (futures and streams are thread-safe and
        # idempotent) so no client blocks for the full wedge; slot and
        # queue bookkeeping stays loop-owned and is reconciled by the
        # dying loop's drain / the restart
        for sl in list(self._host):
            if sl is None:
                continue
            if sl.req["stream"] is not None:
                sl.req["stream"].put(None)
            _fail_future(sl.req["future"], err)
        adm = self._adm
        if adm is not None:
            if adm.req["stream"] is not None:
                adm.req["stream"].put(None)
            _fail_future(adm.req["future"], err)
        # an armed/active capture is a waiter too: fail its future in
        # bounded time like every other (idempotent — if the wedged
        # dispatch ever returns, the loop's _finish_profile resolves
        # second and loses the race); trace/state cleanup stays
        # loop-owned, consistent with the slot bookkeeping above
        pr = self._profile
        if pr is not None:
            _fail_future(pr["future"], err)
        # _pending snapshot may race the unsticking loop's own drain
        # (deque mutated mid-iteration) — retry; whoever wins, both
        # sides fail futures idempotently with comparable errors
        pending = []
        for _ in range(3):
            try:
                pending = list(self._pending)
                break
            except RuntimeError:
                continue
        for req in pending:
            if req["stream"] is not None:
                req["stream"].put(None)
            _fail_future(req["future"], err)
        # requests still parked in the submit queue (enqueued during
        # the wedge, never pumped): fail their futures IN PLACE — the
        # items stay queued so the loop's own drain stays the single
        # owner of queue removal, and _pump_queue skips done futures
        # if the runtime ever unsticks
        with self._queue.mutex:
            parked = [r for r in self._queue.queue if isinstance(r, dict)]
        for req in parked:
            if req["stream"] is not None:
                req["stream"].put(None)
            _fail_future(req["future"], err)

    def _maybe_restart(self) -> bool:  # graftcheck: runs-on(loop)
        """One bounded restart of a dead drive loop: rebuild the device
        carry from scratch (the old pytree may have died mid-donation)
        and start a fresh thread.  Refuses when closing/abandoned, or
        when the loop died again without resolving a single dispatch
        since the last restart."""
        if self._abandoned or self._stop.is_set():
            return False
        if self._dist is not None:
            # a lone restarted process would rebuild a FRESH local
            # carry against a gang mid-sequence — guaranteed
            # divergence.  Stay down; the fleet manager replaces the
            # whole gang (gang-coordinated restart is the named
            # follow-up).
            self._unhealthy_reason = (
                "drive loop died in a distributed gang; watchdog "
                "restarts are disabled (a lone fresh carry would "
                "diverge from the gang) — restart the gang"
            )
            return False
        d = self._stats["dispatches"]
        if (self._dispatches_at_restart is not None
                and d <= self._dispatches_at_restart):
            self._unhealthy_reason = (
                "drive loop died again with no progress since the last "
                "watchdog restart; staying down"
            )
            return False
        self._dispatches_at_restart = d
        # the dead loop's finally-drain already failed every waiter;
        # re-run the teardown idempotently in case it died inside it
        err = self._broken or EngineStalled("drive loop died")
        self._inflight.clear()
        for i in range(len(self._host)):
            self._finish(i, error=err)
        self._fail_admission(err)
        self._drain_pending(err)
        self._host = [None] * self.slots
        self._busy_since = None
        self._dstate = self._fresh_dstate()
        if self._pool is not None:
            # the carry was rebuilt from scratch (fresh zero pages):
            # every host-side mapping/pin is stale — forget it all
            self._pool.reset()
        self._stats["watchdog_restarts"] += 1
        self.recorder.instant("watchdog_restart", track="engine.watchdog")
        self._exit_loop.clear()
        self._broken = None
        self._unhealthy_reason = None
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        return True
