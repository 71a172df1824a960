"""LM serving: HTTP generation over the KV-cache decode path.

The reference framework ends at batch inference artifacts; a user
replacing it still needs to SERVE the model they trained.  This daemon
(`mlcomp-tpu serve`) is that missing piece, built TPU-first:

- **static shapes**: prompts are left-padded into length buckets and
  requests are padded into batch-size buckets, so the whole serving
  surface compiles into a small, bounded set of programs (XLA retraces
  nothing at request time; first hit per bucket pays the compile, and
  `--warmup` precompiles the configured buckets at startup);
- **continuous batching**: a fixed pool of decode slots runs one
  compiled single-token step; a new request prefills alone and JOINS
  the running decode at the next step boundary, finished rows free
  their slot immediately, and tokens stream out as they land
  (``"stream": true`` → SSE).  Batching is where serving throughput
  lives (measured on v5e, 1.2B: B=8 decodes ~3.4× the tokens/s of B=1)
  and token-granularity join means a long generation never blocks a
  later arrival — see mlcomp_tpu/engine.py;
- **weight residency**: weights load once, optionally int8-quantized
  with the Pallas kernel consuming them directly (``--quantize kernel``,
  the measured B=1 win) or pre-cast to bf16;
- **per-request sampling**: temperature/top-k/top-p/eos_id ride the
  compiled program as per-ROW traced arrays (generation.py's rowwise
  path; eos compares broadcast, -1 = no eos), so a request can
  override the service defaults at ZERO recompile cost and mixed-knob
  requests batch together; ``pad_id`` stays service-level (it is
  structural).

Checkpoints resolve exactly like the generate executor: an explicit
``--ckpt`` directory, or the ModelStorage layout (``--storage-task``)
the train executor writes.

HTTP surface (stdlib http.server, same conventions as report/server.py):

    POST /generate  {"prompt": [ids...], "max_new_tokens": 64,
                     "temperature": 0.8, "top_k": 50, "top_p": 0.95,
                     "eos_id": 2, "logprobs": true}
        -> {"ids": [...generated ids only...], "latency_ms": ...,
            "logprobs": [...raw-model log-probs per emitted token...]}
        (sampling/eos/logprobs fields optional; logprobs are
        log_softmax of the unfiltered logits — comparable across
        sampling settings; with ``--prefix-cache`` responses carry
        ``cache_hit_tokens``, the prompt tokens whose prefill the
        host-RAM prefix KV cache skipped)
        (an optional ``"deadline_s"`` bounds the request end to end,
        clamped to ``--request-timeout`` — past it the engine retires
        it at the next dispatch boundary and the response is 504
        ``deadline_exceeded``; when admission
        control is configured (``--max-queue-depth`` /
        ``--max-concurrent-requests``) overload fast-fails with 429 +
        ``Retry-After`` derived from live per-token latency — see
        docs/serving.md "Failure semantics")
    GET  /healthz   -> {"ok": true, "ready": true, "model": ...,
                        "queue_depth": ...,
                        "latency": {p50/p95/p99 ttft + per-token ms},
                        "engine": {..., "pipeline": overlap metrics}}
        (503 with ``"ok": false`` while the engine watchdog reports
        the drive loop stalled/crashed; recovers after its bounded
        restart.  ``ready`` is readiness, distinct from liveness:
        false while warmup compiles run or the daemon is draining —
        the fleet router routes around a not-ready replica without
        the manager restarting it.  Sharded daemons carry a ``mesh``
        block — axis names/sizes, process count/index, coordinator
        flag; a ``serve --distributed`` FOLLOWER answers
        ``ready: false`` so only the gang's coordinator takes
        traffic)
    POST /drain     {"draining": true|false} -> flip readiness for the
        scale-down handshake: a draining daemon finishes in-flight
        work, stays ok, and advertises ready=false
    GET  /cache/stats -> prefix-cache hit/miss/eviction/byte counters
        (404 unless the service was built with ``prefix_cache=True``)
    GET  /metrics   -> Prometheus text exposition (mlcomp_tpu/obs):
        engine dispatch/pipeline counters, TTFT/per-token histograms,
        prefix-cache counters — scrape-ready (docs/observability.md)
    GET  /trace?last_ms=N -> the engine flight recorder's Chrome
        trace-event JSON (Perfetto-loadable): dispatch issue/resolve
        spans, in-flight dispatch async spans, prefill chunks,
        prefix-cache lookups/captures, per-request lifecycle spans.
        ``?trace_id=<32 hex>`` / ``?rid=N`` restrict the export to ONE
        request's events — the id every response echoes (requests
        inherit the client's W3C ``traceparent`` trace id, or mint
        one at submit)
    GET  /slo -> declarative SLO status (mlcomp_tpu/obs/slo.py):
        fast/slow-window burn rates, breach state, and the live
        windowed measurement per objective (TTFT p95, per-token p50,
        reject rate, engine-healthy uptime by default;
        ``--slo-config`` overrides).  404 when the history sampler is
        disabled (``--metrics-history-interval 0``)
    GET  /metrics/history?window_s=N -> the bounded metrics-history
        ring (mlcomp_tpu/obs/history.py) as JSON: per-interval counter
        deltas, gauge points, and materialized histogram quantiles —
        rate/trend queries with no external Prometheus.  404 when
        disabled
    GET  /profile?dispatches=N -> arm a windowed jax.profiler capture
        around the next N dispatch boundaries, parse the xplane with
        the dependency-free reader (obs/devprof.py) and answer with
        the device-time attribution JSON: device_time_ms, host_gap_ms,
        kernel breakdown, per-dispatch-family roofline utilization.
        The capture's device spans also merge into the flight
        recorder, so a /trace fetch afterwards renders host spans
        aligned above the actual device program spans.  Needs live
        decode traffic to complete (the window is dispatch-gated).
        (409 while another capture is armed or in flight)

``MLCOMP_TPU_SERVE_TOKEN`` (optional) demands ``Authorization: Bearer``
on every route, mirroring the report server's auth.
"""

from __future__ import annotations

import json
import os
import queue
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FutTimeout
from typing import Any, Dict, Optional, Sequence

import numpy as np

from mlcomp_tpu.engine import (
    DeadlineExceeded,
    DecodeEngine,
    NotCoordinator,
    ProfileBusy,
    bucket,
)
from mlcomp_tpu.utils.chips import device_summary
from mlcomp_tpu.utils.trace import (
    filter_export,
    make_trace_id,
    parse_traceparent,
    valid_trace_id,
)


class BackpressureError(RuntimeError):
    """Admission control rejected the request (bounded queue or
    concurrency cap): fast-fail with a drain estimate instead of
    unbounded queueing.  HTTP maps this to 429 + ``Retry-After``."""

    def __init__(self, msg: str, reason: str, retry_after_s: float):
        super().__init__(msg)
        self.reason = reason
        self.retry_after_s = float(retry_after_s)


class GenerationService:
    """The serving front of one ``DecodeEngine``: validation, sampling
    defaults, admission control, warmup, and the scrape registry.

    The engine's loop thread owns all JAX work (single-stream dispatch —
    the TPU runs one program at a time anyway); HTTP handler threads
    just enqueue requests and wait on futures.
    """

    def __init__(
        self,
        model,
        variables,
        batch_sizes: Sequence[int] = (1, 2, 4, 8),
        prompt_buckets: Sequence[int] = (128, 256, 512, 1024),
        max_new_buckets: Sequence[int] = (32, 128),
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        eos_id: Optional[int] = None,
        pad_id: int = 0,
        quantize: "bool | str" = False,
        seed: int = 0,
        mesh=None,
        repetition_penalty: float = 1.0,
        batcher: str = "auto",
        steps_per_dispatch: "Optional[int | str]" = None,
        prefill_chunk: int = 256,
        prefix_cache: bool = False,
        prefix_cache_bytes: int = 1 << 31,
        engine_pipeline_depth: Optional[int] = None,
        engine_fused_admission: Optional[bool] = None,
        flight_recorder_events: Optional[int] = 32768,
        request_timeout_s: float = 600.0,
        max_queue_depth: int = 0,
        max_concurrent_requests: int = 0,
        dispatch_stall_timeout: Optional[float] = None,
        kv_layout: str = "dense",
        kv_page_tokens: Optional[int] = None,
        kv_pages: Optional[int] = None,
        max_slots: Optional[int] = None,
        metrics_history_interval: Optional[float] = 5.0,
        slo_config: Optional[Dict[str, Any]] = None,
        dist=None,
        phase: str = "both",
    ):
        from mlcomp_tpu.obs.metrics import Registry
        from mlcomp_tpu.ops.quant import quantize_params

        self.model = model
        # multi-chip serving: a jax.sharding.Mesh (from load_service's
        # mesh config).  Weights arrive already sharded; prompts get the
        # mesh's batch sharding; the KV cache shards by XLA propagation
        # from the tp-sharded K/V projections.  The Pallas paths
        # (quantize="kernel", model kv_quant) run inside shard_map
        # islands under the mesh (ops/quant.sharded_quant_matmul,
        # decode_attention.sharded_decode_attention) — validated here
        # for the layouts those wrappers support.
        self.mesh = mesh
        # multi-host serve gang (serve --distributed): a
        # parallel/distributed.BoundaryChannel.  Process 0 (the
        # coordinator) owns the HTTP front door and submit queue;
        # every other process is a FOLLOWER that replays the
        # coordinator's broadcast boundary decisions and answers
        # /healthz as ready:false so the fleet router never targets it.
        self.dist = dist
        if dist is not None and mesh is None:
            raise ValueError(
                "distributed serving needs a mesh (--mesh): the gang "
                "runs one SPMD program over the global device mesh"
            )
        if mesh is not None:
            dbatch = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
            bad = [b for b in batch_sizes if b % dbatch]
            if bad:
                raise ValueError(
                    f"batch sizes {bad} don't divide the mesh's data axes "
                    f"(dp*fsdp = {dbatch}); fix --batch-sizes"
                )
            pallas = getattr(model, "kv_quant", False) or (
                str(quantize).strip().lower() == "kernel"
            )
            if pallas and mesh.shape.get("fsdp", 1) > 1:
                # fsdp scatters weights across an axis the kernel
                # islands don't model; tp is the sharding that matters
                # for serving big models
                raise ValueError(
                    "quantize='kernel' / kv_quant need a tp/dp mesh; "
                    "fsdp-sharded serving runs bf16 or entry-dequant int8"
                )
            tp = mesh.shape.get("tp", 1)
            heads = getattr(model, "heads", None)
            if pallas and tp > 1 and heads:
                kv_heads = getattr(model, "kv_heads", None) or heads
                if heads % tp or kv_heads % tp:
                    raise ValueError(
                        f"tp={tp} must divide heads ({heads}) and kv_heads "
                        f"({kv_heads}) for the Pallas serving kernels"
                    )
        self.batch_sizes = tuple(sorted(batch_sizes))
        self.prompt_buckets = tuple(sorted(prompt_buckets))
        self.max_new_buckets = tuple(sorted(max_new_buckets))
        self.pad_id = int(pad_id)
        # pad_id is structural (traces into the program); the sampling
        # knobs AND eos ride as per-ROW traced arrays (generation.py
        # rowwise path / broadcast eos compare), so per-request
        # overrides share one compiled program per bucket.  eos row
        # neutral is -1: no vocab id matches, so "no eos" needs no
        # separate program either.
        self.knobs: Dict[str, Any] = {
            "pad_id": int(pad_id),
        }
        self.defaults: Dict[str, Any] = {
            "temperature": float(temperature),
            "top_k": top_k,
            "top_p": top_p,
            "eos_id": eos_id,
            "repetition_penalty": float(repetition_penalty),
        }
        self._neutral_k = int(
            getattr(model, "vocab_size", None) or (1 << 30)
        )
        self.quant_mode = None
        if quantize:
            self.quant_mode = (
                "int8" if quantize is True else str(quantize).strip().lower()
            )
            if self.quant_mode not in ("int8", "kernel"):
                raise ValueError(
                    f"quantize: expected False/'int8'/'kernel', got {quantize!r}"
                )
            variables = {
                **variables,
                "params": quantize_params(variables["params"]),
            }
            if self.quant_mode == "kernel":
                self.knobs["quant_kernel"] = True
        self.variables = variables
        # resilience knobs: every request gets a deadline (default: the
        # request timeout — the old hardcoded 600 s futures, made
        # configurable and engine-enforced), and admission control
        # fast-fails past the bounded queue/concurrency caps (0 =
        # unbounded, the historical behavior)
        self.request_timeout_s = float(request_timeout_s)
        if self.request_timeout_s <= 0:
            raise ValueError(
                f"request_timeout_s must be positive, got {request_timeout_s}"
            )
        self.max_queue_depth = int(max_queue_depth or 0)
        self.max_concurrent_requests = int(max_concurrent_requests or 0)
        self._rejects = {
            "queue_full": 0, "concurrency": 0, "no_free_pages": 0,
        }
        # paged device KV (mlcomp_tpu/kvpool): admission control gains
        # the free-page budget as a first-class resource — a request
        # whose worst-case page need exceeds what is free, reclaimable,
        # and not already spoken for by the queued backlog fast-fails
        # with 429 ``no_free_pages`` (always on for the paged layout:
        # unlike the opt-in queue caps, pool exhaustion is a hard
        # physical bound, and queueing past it is just a slower 429)
        # disaggregated serving role (docs/serving.md "Disaggregated
        # serving"): "both" is the monolithic daemon; "prefill" runs
        # the admission core only and answers POST /prefill with
        # KV-page handoff blobs; "decode" is a paged daemon that
        # additionally admits handoffs via POST /import — skipping
        # prefill entirely, bit-identical to a local admission.
        self.phase = str(phase)
        if self.phase not in ("both", "prefill", "decode"):
            raise ValueError(
                f"phase must be 'both', 'prefill', or 'decode'; got "
                f"{phase!r}"
            )
        if self.phase != "both" and (mesh is not None or dist is not None):
            raise ValueError(
                "phase-split serving is single-process single-chip "
                "for now (sharded prefill tiers and gang imports "
                "are named follow-ups); drop --mesh/--distributed "
                "or phase"
            )
        if self.phase == "decode" and kv_layout != "paged":
            raise ValueError(
                "phase='decode' needs kv_layout='paged': handoff "
                "imports land as pages in the engine's PagePool"
            )
        self.kv_layout = str(kv_layout)
        # the scrape registry behind GET /metrics: the engine (and its
        # prefix cache) register collectors into it below; the service
        # contributes its own admission counters — one exposition per
        # daemon
        self.metrics = Registry()
        self.metrics.register_collector(self._collect_metrics)
        # observability spine: the metrics-history sampler thread
        # (GET /metrics/history) and the SLO burn-rate engine
        # (GET /slo) built on it.  The SLO config is validated HERE —
        # before the engine spins up any threads — so a malformed
        # --slo-config fails construction with a clear message instead
        # of surfacing at the first evaluation tick.
        self.history = None
        self.slo = None
        self._history_interval = (
            float(metrics_history_interval)
            if metrics_history_interval else 0.0
        )
        if self._history_interval < 0:
            raise ValueError(
                f"metrics_history_interval must be >= 0 (0 disables), "
                f"got {metrics_history_interval}"
            )
        # keep the RAW override for SLOEngine (validate_config is how
        # it learns which SLOs are disabled — feeding it an already-
        # validated config would re-merge the defaults and resurrect
        # them); the early call exists purely to fail fast
        self._slo_config = slo_config
        if self._history_interval > 0:
            from mlcomp_tpu.obs.slo import validate_config

            validate_config(slo_config)
        elif slo_config is not None:
            raise ValueError(
                "slo_config needs the metrics-history sampler; don't "
                "set metrics_history_interval to 0 with an SLO config"
            )
        # readiness vs liveness: ``ok`` (the watchdog verdict) answers
        # "should the manager restart this replica"; ``ready`` answers
        # "should the router send it traffic".  A daemon mid-warmup or
        # deliberately draining is NOT ready but IS ok — killing it
        # would be wrong, routing to it would be wrong, and one bit
        # cannot express both.
        self._draining = False
        self._warming = False
        # the one batcher: the token-granularity slot engine
        # (mlcomp_tpu/engine.py).  The keyword stays while
        # benchmark/serving.py passes it; it selects nothing.
        if batcher not in ("auto", "continuous"):
            raise ValueError(
                f"batcher: expected 'auto'/'continuous', got {batcher!r}"
            )
        self.prefix_cache = None
        if prefix_cache:
            # host-RAM prefix KV cache (mlcomp_tpu/cache): host row
            # inserts don't compose with a sharded cache — fail at
            # construction, not per request
            if mesh is not None:
                raise ValueError(
                    "the prefix KV cache is single-chip for now; drop "
                    "prefix_cache or the mesh"
                )
            from mlcomp_tpu.cache import PrefixKVCache

            self.prefix_cache = PrefixKVCache(
                max_bytes=int(prefix_cache_bytes)
            )
        # SERVICE default: adaptive dispatch depth — the drive
        # loop picks K per boundary from the live queue-depth /
        # occupancy signals (shallow queues small K for TTFT, deep
        # queues large K for dispatch amortization).  An explicit
        # --engine-steps-per-dispatch PINS K (the bisect override).
        if steps_per_dispatch is None:
            steps_per_dispatch = "adaptive"
        self.engine = DecodeEngine(
            model, self.variables,
            slots=self.batch_sizes[-1],
            prompt_buckets=self.prompt_buckets,
            max_new_cap=self.max_new_buckets[-1],
            pad_id=self.pad_id,
            quant_kernel=self.quant_mode == "kernel",
            seed=seed,
            steps_per_dispatch=steps_per_dispatch,
            prefill_chunk=prefill_chunk,
            mesh=mesh,
            prefix_cache=self.prefix_cache,
            pipeline_depth=engine_pipeline_depth,
            fused_admission=engine_fused_admission,
            flight_recorder_events=flight_recorder_events,
            metrics=self.metrics,
            dispatch_stall_timeout=dispatch_stall_timeout,
            kv_layout=kv_layout,
            kv_page_tokens=kv_page_tokens,
            kv_pages=kv_pages,
            max_slots=max_slots,
            dist=dist,
            prefill_only=self.phase == "prefill",
        )
        # the engine materialized its own decode-ready tree
        # (entry-dequant + kernel folding); nothing reads the
        # original — keeping it pinned would double weight HBM
        # residency for quantized services
        self.variables = self.engine.variables
        if self._history_interval > 0:
            from mlcomp_tpu.obs.history import MetricsHistory
            from mlcomp_tpu.obs.slo import SLOEngine

            self.history = MetricsHistory(
                self.metrics, interval_s=self._history_interval,
            )
            self.slo = SLOEngine(
                self.history, config=self._slo_config,
                registry=self.metrics,
                recorder=self.engine.recorder,
            )
            # burn rates re-evaluate at every sampler tick — breaches
            # flip (and record their flight-recorder instant) with or
            # without scrape traffic
            self.history.add_callback(self.slo.evaluate)

    # ------------------------------------------------------------- public

    def submit(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: int,
        temperature: Optional[float] = None,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        eos_id: Optional[int] = None,
        logprobs: bool = False,
        repetition_penalty: Optional[float] = None,
        stream: Optional["queue.Queue"] = None,
        deadline_s: Optional[float] = None,
        trace_id: Optional[str] = None,
    ) -> Future:
        """Enqueue one generation request; resolves to a list of the
        GENERATED ids (prompt excluded, truncated at the request's
        ``max_new_tokens``; pads after EOS trimmed).

        Per-request sampling knobs default to the service config; they
        ride the compiled program as per-row arrays, so overriding them
        costs no recompile and mixed-knob requests batch together.

        ``stream``: a ``queue.Queue`` that receives ``{"token",
        "logprob", "step"}`` dicts as each token lands, then ``None`` —
        the transport behind the HTTP SSE endpoint.

        ``deadline_s`` (default — and upper clamp — is the service's
        ``request_timeout_s``) bounds the request end to end — past it
        the engine retires the request at the next dispatch boundary
        and the future fails with ``DeadlineExceeded`` (HTTP: 504).
        Admission control may reject BEFORE queueing with
        ``BackpressureError`` (HTTP: 429 + ``Retry-After``) when the
        bounded queue or concurrency cap is hit.

        ``trace_id`` (optional): a W3C-shape 32-hex trace
        id to adopt (the HTTP layer passes the client's ``traceparent``
        id here); minted when absent.  The id is echoed in the result
        and threads through every flight-recorder span the request
        touches — ``GET /trace?trace_id=`` pulls exactly this
        request's events."""
        if trace_id is not None and not valid_trace_id(trace_id):
            raise ValueError(
                f"trace_id must be 32 lowercase hex chars (W3C trace "
                f"context), got {trace_id!r}"
            )
        ids = [int(t) for t in prompt_ids]
        if not ids:
            raise ValueError("prompt must be non-empty")
        n_new = int(max_new_tokens)
        if n_new <= 0:
            raise ValueError("max_new_tokens must be positive")
        t = self.defaults["temperature"] if temperature is None else float(
            temperature
        )
        if not 0.0 <= t <= 100.0:
            raise ValueError(f"temperature must be in [0, 100], got {t}")
        k = self.defaults["top_k"] if top_k is None else int(top_k)
        if k is not None and k < 1:
            raise ValueError(f"top_k must be >= 1, got {k}")
        if k is not None:
            # anything >= vocab is a no-op; clamping here keeps a huge
            # client value from overflowing the int32 knob row in the
            # engine (which would fail the whole co-batched group)
            k = min(k, self._neutral_k)
        p = self.defaults["top_p"] if top_p is None else float(top_p)
        if p is not None and not 0.0 < p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {p}")
        rp = (
            self.defaults["repetition_penalty"]
            if repetition_penalty is None else float(repetition_penalty)
        )
        if not 0.0 < rp <= 10.0:
            raise ValueError(
                f"repetition_penalty must be in (0, 10], got {rp}"
            )
        if not isinstance(logprobs, bool):
            # strict like the other fields: a string "false" silently
            # coercing to True would mask client bugs
            raise ValueError(
                f"logprobs must be a JSON boolean, got {logprobs!r}"
            )
        eos = self.defaults["eos_id"] if eos_id is None else int(eos_id)
        if eos is not None and not 0 <= eos < 2**31:
            if eos == -1 or eos_id is None:
                # -1 is the documented per-request "no eos" opt-out
                # (run the full budget even when the service has a
                # default); a negative SERVICE default keeps its
                # historical never-matches no-op meaning
                eos = None
            else:
                raise ValueError(
                    f"eos_id must be in [0, 2^31), or -1 for none; "
                    f"got {eos}"
                )
        # validate bucket fit NOW (caller thread) so errors surface as
        # request errors, not loop crashes
        bucket(len(ids), self.prompt_buckets, "prompt length")
        bucket(n_new, self.max_new_buckets, "max_new_tokens")
        self._admission_check(ids, n_new)
        # per-request deadlines may only TIGHTEN the operator's
        # --request-timeout budget: a slot is a shared resource,
        # so a client cannot extend its hold past the service cap
        eff_deadline = self.request_timeout_s
        if deadline_s is not None:
            eff_deadline = min(float(deadline_s), eff_deadline)
        return self.engine.submit(
            ids, n_new, temperature=t, top_k=k, top_p=p, eos_id=eos,
            logprobs=logprobs, repetition_penalty=rp, stream=stream,
            deadline_s=eff_deadline, trace_id=trace_id,
        )

    def generate(self, prompt_ids, max_new_tokens, **knobs):
        return self.submit(prompt_ids, max_new_tokens, **knobs).result()

    def cancel(self, rid: int) -> bool:
        """Cancel a live request by rid (the ``rid`` attribute of a
        submitted Future) — the HTTP layer calls this when a streaming
        client disconnects."""
        return self.engine.cancel(rid)

    def import_pages(
        self,
        blob: bytes,
        stream: Optional["queue.Queue"] = None,
        deadline_s: Optional[float] = None,
        trace_id: Optional[str] = None,
    ) -> Future:
        """Admit a disaggregated handoff (behind ``POST /import``):
        validate the blob against this engine's paged geometry (typed
        ``HandoffError`` on a truncated/mismatched transfer — nothing
        allocated), run the same admission-control gates a local
        submit passes (free-page budget, queue/concurrency caps), and
        queue the import.  The future resolves to the standard
        generation result; decode tokens are bit-identical to a local
        admission of the same prompt."""
        if self.engine._pool is None:
            raise ValueError(
                "handoff import needs a paged engine "
                "(phase='decode', or any --kv-layout paged daemon)"
            )
        parsed = self.engine.validate_handoff(blob)
        meta = parsed[0]
        self._admission_check(meta["ids"], int(meta["n_new"]))
        eff_deadline = self.request_timeout_s
        if deadline_s is not None:
            eff_deadline = min(float(deadline_s), eff_deadline)
        return self.engine.import_pages(
            blob, stream=stream, deadline_s=eff_deadline,
            trace_id=trace_id, parsed=parsed,
        )

    def _per_token_p50_ms(self) -> Optional[float]:
        eng = self.engine
        try:
            samples = list(eng._lat_tok)
        except RuntimeError:
            # the loop thread appended mid-iteration; a reject under
            # exactly that load still needs SOME answer, not a 500
            samples = []
        if not samples:
            return None
        return float(np.median(np.asarray(samples)))

    def _retry_after_s(self, needed_pages: Optional[int] = None) -> float:
        """Drain estimate behind 429's ``Retry-After``.  Slot-pool
        heuristic (dense): (waiting + active) requests × the mean
        tokens each emits × p50 per-token ms, spread over the slot
        pool.  PAGED (``needed_pages`` set): projected page-free rate
        instead — walk the active slots soonest-retiring first,
        accumulate the pages each will return (its table row's
        non-reserved entries; shared pages are counted optimistically —
        a lower bound on the wait beats an hour-long guess), and answer
        the remaining-token clock of the slot whose retirement finally
        covers the need.  Falls back to 1 s before any latency samples
        exist; clamped to [1, 60] so a pathological estimate never
        tells clients to go away for an hour."""
        eng = self.engine
        per_tok = self._per_token_p50_ms()
        if per_tok is None:
            return 1.0
        if needed_pages is not None and eng._pool is not None:
            from mlcomp_tpu.kvpool import RESERVED_PAGES

            try:
                pool = eng._pool
                freed = pool.alloc.free_pages + pool.reclaimable_pages()
                rows = sorted(
                    (sl.remaining, i)
                    for i, sl in enumerate(list(eng._host))
                    if sl is not None
                )
                eta_tokens = None
                for remaining, i in rows:
                    freed += int(
                        (pool.tables[i] >= RESERVED_PAGES).sum()
                    )
                    if freed >= needed_pages:
                        eta_tokens = remaining
                        break
                if eta_tokens is None:
                    return 60.0
                return float(
                    min(max(eta_tokens * per_tok / 1e3, 1.0), 60.0)
                )
            except RuntimeError:
                # loop thread resized a registry/table dict mid-walk
                # (same torn-read race _page_budget_check and the
                # engine's _pool_stats tolerate): fall back to the
                # slot-pool heuristic below — a rough Retry-After
                # still beats turning this 429 into a 500
                pass
        st = eng._stats
        finished = max(1, eng._lat_ttft_n)
        mean_tokens = max(1.0, st["emitted_tokens"] / finished)
        waiting = eng._queue.qsize() + len(eng._pending) + 1
        active = sum(1 for s in eng._host if s is not None)
        eta = (waiting + active) * mean_tokens * per_tok / (
            eng.slots * 1e3
        )
        return float(min(max(eta, 1.0), 60.0))

    def _reject(self, reason: str, msg: str,
                needed_pages: Optional[int] = None) -> None:
        self._rejects[reason] += 1
        self.engine.recorder.instant(
            "reject", track="service", reason=reason,
        )
        raise BackpressureError(
            msg, reason, self._retry_after_s(needed_pages=needed_pages)
        )

    def _page_budget_check(self, ids, n_new: int) -> None:
        """Free-page admission gate (paged layout, always on): the
        request's INITIAL page need — prefill span plus one dispatch
        of decode lookahead, the lazy-allocation admission currency —
        against what is free plus reclaimable minus the queued
        backlog's own initial needs.  Pages commit only at insert, so
        without the backlog term a flood would all pass the same
        free-page reading and queue unboundedly.  Decode pages past
        the lookahead allocate lazily as cursors cross page boundaries
        (that overcommit is why paged admits strictly more concurrent
        streams at equal HBM); a pool that runs dry at such a crossing
        is the engine's BOUNDED mid-stream failure, not this gate's
        concern.  Approximate like the other caps (racing submits may
        both pass); the engine's own boundary gate defers or fails
        whatever slips through."""
        eng = self.engine
        try:
            need = eng._pages_initial({"ids": ids, "n_new": n_new})
            pool = eng._pool
            avail = pool.alloc.free_pages + pool.reclaimable_pages()
            backlog = 0
            for r in list(eng._pending):
                backlog += eng._pages_initial(r)
            with eng._queue.mutex:
                parked = [
                    r for r in eng._queue.queue if isinstance(r, dict)
                ]
            for r in parked:
                backlog += eng._pages_initial(r)
            adm = eng._adm
            if adm is not None:
                backlog += eng._pages_initial(adm.req)
        except RuntimeError:
            return  # torn read mid-mutation: admit, the engine re-gates
        if need <= avail - backlog:
            return
        self._reject(
            "no_free_pages",
            f"request needs {need} KV pages at admission; "
            f"{max(avail - backlog, 0)} free after the queued backlog "
            f"(pool: {pool.alloc.total_pages})",
            needed_pages=need + backlog,
        )

    def _admission_check(self, ids=None, n_new: Optional[int] = None):
        """Admission fast-fail: the paged layout's
        free-page budget first (the hard physical resource), then the
        opt-in bounded queue / concurrency caps.  Approximate by design
        — two racing submits may both pass a cap-1 check — which is the
        standard admission-control trade: the bound is 'about N', never
        a hung client."""
        eng = self.engine
        if eng._pool is not None and ids is not None:
            self._page_budget_check(ids, int(n_new))
        if self.max_queue_depth <= 0 and self.max_concurrent_requests <= 0:
            return
        depth = eng._queue.qsize() + len(eng._pending)
        if 0 < self.max_queue_depth <= depth:
            self._reject("queue_full", (
                f"submit queue is full ({depth} >= max_queue_depth="
                f"{self.max_queue_depth})"
            ))
        active = sum(1 for s in eng._host if s is not None)
        inflight = depth + active + (1 if eng._adm is not None else 0)
        if 0 < self.max_concurrent_requests <= inflight:
            self._reject("concurrency", (
                f"{inflight} requests in flight >= "
                f"max_concurrent_requests={self.max_concurrent_requests}"
            ))

    def set_draining(self, draining: bool) -> bool:
        """Flip the drain bit (behind ``POST /drain``): a draining
        daemon keeps serving in-flight work and answers ``/healthz``
        200/ok, but advertises ``ready: false`` so the fleet router
        routes new traffic elsewhere while the manager lets it finish —
        the scale-down handshake."""
        self._draining = bool(draining)
        return self._draining

    def warmup(self) -> int:
        """Precompile the hot programs by RUNNING a dummy generation per
        prompt bucket (jax.jit is lazy and AOT-lowered executables don't
        seed the jit call cache, so only a real call makes later
        requests hit compiled code), then the engine's ladder, fused
        and prefix programs.  ``ready`` reads false for the
        duration — a router polling mid-warmup routes around the
        compiling replica instead of queueing behind its compiles."""
        self._warming = True
        try:
            return self._warmup_inner()
        finally:
            self._warming = False

    def _warmup_inner(self) -> int:
        if self.dist is not None and not self.engine.is_coordinator:
            # followers compile by REPLAY: the coordinator's warmup
            # submissions and its warm ctrl record arrive over the
            # boundary channel and run on the follower's loop
            # thread in the same order — a local warmup here would
            # issue SPMD programs off-loop and desequence the gang
            return 0
        # one dummy request per prompt bucket compiles that bucket's
        # prefill; the first compiles the shared insert + step too
        n_new = min(2, self.engine.max_new_cap)
        futs = [
            self.engine.submit([1] * s, n_new, _count=False)
            for s in self.prompt_buckets
        ]
        for f in futs:
            # the configurable request timeout, not a magic 600:
            # warmup compiles, so the cap matters on slow backends
            f.result(timeout=self.request_timeout_s)
        # prefix-cache capture/insert programs (cheap: no model
        # trace), the K LADDER's plain dispatch programs (adaptive
        # engines: one real compile per rung, so a controller
        # switch mid-serving is a dict lookup), and the fused
        # prefill+decode dispatches (real compiles — one per chunk
        # width per rung) — without this the first real request /
        # first overlapped admission / first K switch pays their
        # compile on the engine loop thread mid-serving
        if self.dist is not None:
            # distributed: the warm fns must run ON the loop
            # thread at a broadcast boundary so every process
            # compiles them at the same point in the device
            # sequence
            return len(futs) + self.engine.warm_on_loop().result(
                timeout=self.request_timeout_s
            )
        return (len(futs) + self.engine.warm_prefix_fns()
                + self.engine.warm_dispatch_fns()
                + self.engine.warm_fused_fns()
                + self.engine.warm_export_fns())

    def stats(self) -> Dict[str, Any]:
        # the engine is the single counter of requests (warmup's dummy
        # submissions are excluded there)
        eng = self.engine.stats()
        out = {
            "requests": eng["requests"],
            "queue_depth": eng.pop("queue_depth"),
            "quantize": self.quant_mode,
            "batcher": "continuous",
            # the engine's watchdog verdict IS the daemon's health
            # (behind /healthz's 200-vs-503)
            "healthy": eng.get("healthy", True),
            "rejected": dict(self._rejects),
            "request_timeout_s": self.request_timeout_s,
            # the disaggregation role: the router routes fresh prompts
            # to prefill replicas and page handoffs to decode replicas
            # off this field (the registry mirrors it)
            "phase": self.phase,
            # the devices THIS process holds (platform, device_kind,
            # count, visible chips): launchers and smoke tests learn
            # the device here instead of touching JAX themselves
            "device": device_summary(),
            # request-latency percentiles (p50/p95/p99 TTFT and
            # per-token) ride at the TOP level too: the /healthz
            # payload and the report server's /api/serving proxy read
            # them without digging through the engine section
            "latency": eng.get("latency"),
        }
        if "kv_pool" in eng:
            # paged-KV occupancy at the top level: /healthz readers
            # (and the report proxy) see pages free/used and the
            # live elastic slot count without digging
            out["kv_pool"] = eng["kv_pool"]
            out["live_slots"] = eng.get("live_slots")
        if "mesh" in eng:
            # sharded serving at the top level: axis names/sizes,
            # process count/index, coordinator flag — the /healthz
            # mesh block fleet operators read to find the gang's
            # front door
            out["mesh"] = eng["mesh"]
        out["engine"] = eng
        if self.slo is not None:
            # the SLO verdict rides /healthz: which objectives are
            # burning budget and how fast, without a second fetch
            out["slo"] = self.slo.summary()
        if self.history is not None:
            out["metrics_history"] = self.history.stats()
        # readiness is liveness minus "can take NEW traffic": warmup
        # compiles and deliberate drains clear it without touching ok —
        # the router reads ready, the manager reads ok.  A distributed
        # FOLLOWER is never ready (it owns no submit queue; it is
        # healthy while it replays the coordinator's boundaries), so
        # the fleet router only ever targets the gang's front door.
        out["draining"] = self._draining
        out["ready"] = bool(
            out["healthy"] and not self._draining and not self._warming
            and (self.dist is None or self.dist.is_coordinator)
        )
        return out

    def cache_stats(self) -> Optional[Dict[str, Any]]:
        """Prefix-cache counters (hits/misses/evictions/bytes), or None
        when the service runs without a prefix cache — the payload
        behind GET /cache/stats."""
        if self.prefix_cache is None:
            return None
        return self.prefix_cache.stats()

    def _collect_metrics(self) -> None:
        """Scrape-time collector for the service-level counters (the
        engine registers its own)."""
        m = self.metrics
        m.gauge(
            "mlcomp_service_info",
            "Service configuration (value is always 1)",
            labelnames=("batcher", "quantize"),
        ).set(1, batcher="continuous", quantize=str(self.quant_mode))
        rej = m.counter(
            "mlcomp_serving_requests_rejected_total",
            "Requests fast-failed by admission control",
            labelnames=("reason",),
        )
        for reason, n in self._rejects.items():
            rej.set_total(n, reason=reason)

    def trace(self, last_ms: Optional[float] = None,
              trace_id: Optional[str] = None,
              rid: Optional[int] = None) -> Dict[str, Any]:
        """The engine flight recorder's Chrome-trace export (behind
        GET /trace).  ``trace_id`` / ``rid`` restrict the export to one
        request's events (lifecycle span, admission spans, cache/
        registry lookups, insert)."""
        body = self.engine.recorder.export(last_ms=last_ms)
        if trace_id is not None or rid is not None:
            body = filter_export(body, trace_id=trace_id, rid=rid)
        return body

    def slo_status(self) -> Dict[str, Any]:
        """The SLO engine's full status (behind GET /slo).  Raises when
        the history sampler is disabled — HTTP maps that to 404."""
        if self.slo is None:
            raise ValueError(
                "SLOs need the metrics-history sampler; this service "
                "was built with metrics_history_interval=0"
            )
        return self.slo.status()

    def metrics_history(self, window_s: Optional[float] = None
                        ) -> Dict[str, Any]:
        """The metrics-history ring as JSON (behind
        GET /metrics/history).  Raises when disabled — HTTP 404."""
        if self.history is None:
            raise ValueError(
                "metrics history is disabled; this service was built "
                "with metrics_history_interval=0"
            )
        return self.history.query(window_s=window_s)

    def profile(self, dispatches: int = 8) -> Future:
        """Arm an on-demand device-profile capture (behind
        GET /profile): resolves to the attribution JSON once the
        engine's next ``dispatches`` dispatch boundaries have been
        captured and parsed.  Raises ``ProfileBusy`` while another
        capture is in flight (HTTP 409)."""
        return self.engine.profile(dispatches=dispatches)

    def profile_cancel(self, fut: Future) -> bool:
        """Best-effort disarm of a not-yet-started capture (the HTTP
        timeout path)."""
        return self.engine.profile_cancel(fut)

    def close(self) -> None:
        if self.history is not None:
            # stop the sampler (and with it the SLO evaluation
            # callbacks) before tearing the engine down
            self.history.close()
        self.engine.close()
        if getattr(self, "_owns_process_mesh", False):
            # load_service installed the mesh process-wide (model code
            # reads current_mesh() for shard_map paths); un-install it so
            # a later mesh-less service or other model code in this
            # process doesn't inherit a stale mesh
            from mlcomp_tpu.parallel.mesh import set_current_mesh

            set_current_mesh(None)
            self._owns_process_mesh = False


# --------------------------------------------------------------- loading


def load_service(
    model_cfg: Dict[str, Any],
    ckpt_dir: Optional[str] = None,
    mesh_cfg: Optional[Dict[str, int]] = None,
    **service_kw,
) -> GenerationService:
    """Build the model, restore weights (weights-only, like the
    infer/valid/generate executors), and wrap in a GenerationService.

    ``mesh_cfg`` (e.g. ``{"tp": 4}``) serves the model SHARDED over a
    device mesh — the path for models too big for one chip: weights get
    the same Megatron tp layout training uses (`parallel/sharding.py`
    rules), the KV cache shards by propagation, and each request batch
    runs as one SPMD program (certified by the driver's dp×tp decode
    dryrun leg).  Init runs under jit with sharded outputs and orbax
    restores directly onto those shardings (io/checkpoint.py), so the
    full model materializes on no single device or host."""
    import jax
    import jax.numpy as jnp

    from mlcomp_tpu.models import create_model
    from mlcomp_tpu.train.optim import create_optimizer
    from mlcomp_tpu.train.state import TrainState, init_model

    model_cfg = dict(model_cfg)
    # ``decode_fused: true`` changes the PARAM layout (fused qkv/gate_up
    # serving projections, models/transformer.py) but checkpoints come
    # from training, which is always unfused: init/restore through the
    # standard layout, then convert once below.  Mesh serving keeps the
    # standard layout (the tp sharding rules map per-projection).
    decode_fused = bool(model_cfg.pop("decode_fused", False))
    if decode_fused and mesh_cfg:
        raise ValueError(
            "decode_fused serving is single-chip (the Megatron tp rules "
            "shard the unfused projections); drop one of them"
        )
    model = create_model(dict(model_cfg))
    example = jnp.zeros((1, 8), jnp.int32)
    # a throwaway optimizer only shapes the TrainState container;
    # restore_eval_state is weights-only and never reads opt_state
    opt = create_optimizer({"name": "sgd", "lr": 0.0})

    def init_fn():
        params, mstate = init_model(
            model, {"x": example}, jax.random.PRNGKey(0)
        )
        return TrainState.create(model.apply, params, opt, mstate)

    mesh = None
    if mesh_cfg:
        from mlcomp_tpu.parallel.mesh import MeshSpec, make_mesh
        from mlcomp_tpu.parallel.sharding import make_sharded_state

        mesh = make_mesh(MeshSpec.from_config(mesh_cfg))
        # install process-wide like the Trainer does: model forward code
        # reads current_mesh() for shard_map-based paths (ring/sp, the
        # pipelined LM's pp stages) — without this they'd silently trace
        # mesh-less and waste those axes
        from mlcomp_tpu.parallel.mesh import set_current_mesh

        set_current_mesh(mesh)
        # sharded from the first byte: init lands directly on the
        # training layout (same spec_for rules), and restore_eval_state
        # places restored arrays onto those shardings — the full model
        # never materializes on one device
        state, _ = make_sharded_state(init_fn, mesh)
    else:
        state = init_fn()
    if ckpt_dir:
        from mlcomp_tpu.io.checkpoint import restore_eval_state

        state = restore_eval_state(ckpt_dir, state)
    variables = state.eval_variables
    if decode_fused:
        from mlcomp_tpu.models.transformer import fuse_decode_params

        model = create_model({**model_cfg, "decode_fused": True})
        variables = {**variables, "params": fuse_decode_params(
            variables["params"]
        )}
    service = GenerationService(
        model, variables, mesh=mesh, **service_kw
    )
    # this service installed the process-wide mesh above; close() resets
    # it (one live mesh-serving GenerationService per process)
    service._owns_process_mesh = mesh is not None
    return service


def resolve_storage_ckpt(project: str, dag_name: str, task: str) -> str:
    """ModelStorage-convention checkpoint dir (what the train executor
    writes); explicit --ckpt wins over this."""
    from mlcomp_tpu.io.storage import ModelStorage

    ms = ModelStorage()
    d = ms.checkpoint_dir(project, dag_name, task)
    if not os.path.isdir(d):
        raise FileNotFoundError(
            f"no checkpoints under {d} (train first, or pass --ckpt)"
        )
    return str(d)


# ------------------------------------------------------------------ HTTP


def make_http_server(
    service: GenerationService,
    host: str = "127.0.0.1",
    port: int = 8900,
    model_name: str = "model",
) -> "ThreadingHTTPServer":
    """Build (without starting) the daemon's HTTP server — the
    non-blocking half of ``serve_http``, reused by tests and
    tools/obs_check.py on an ephemeral port."""
    import hmac
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class Handler(BaseHTTPRequestHandler):
        # HTTP/1.1: persistent connections, so the fleet router's
        # upstream connection pool actually reuses sockets (HTTP/1.0
        # closed after every response — a new TCP handshake per
        # proxied request was the router's measured ceiling).  Every
        # response sets Content-Length; the SSE stream opts out with
        # an explicit Connection: close.
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):  # quiet access log
            pass

        def _json(self, obj, code=200, close=False):
            """``close=True`` for responses sent BEFORE the request
            body was read (403/404/409 early returns): under
            HTTP/1.1 keep-alive the unread body would otherwise be
            parsed as the next request line on this connection."""
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            if close:
                self.send_header("Connection", "close")
            self.end_headers()
            self.wfile.write(body)

        def _reject_429(self, e: "BackpressureError", tid) -> None:
            """The one admission-control 429 shape every POST route
            answers (body + ``Retry-After`` relayed verbatim by the
            fleet router, which also reads it for mark_saturated)."""
            body = json.dumps({
                "error": str(e), "status": "rejected",
                "reason": e.reason,
                "retry_after_s": round(e.retry_after_s, 1),
                "trace_id": tid,
            }).encode()
            self.send_response(429)
            self.send_header("Content-Type", "application/json")
            self.send_header(
                "Retry-After", str(max(1, int(round(e.retry_after_s))))
            )
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _token_ok(self) -> bool:
            secret = os.environ.get("MLCOMP_TPU_SERVE_TOKEN", "")
            if not secret:
                return True
            auth = self.headers.get("Authorization", "")
            return hmac.compare_digest(auth, f"Bearer {secret}")

        def do_GET(self):  # noqa: N802
            if not self._token_ok():
                return self._json({"error": "invalid or missing token"}, 403)
            route, _, query = self.path.partition("?")
            if route == "/healthz":
                st = service.stats()
                ok = bool(st.get("healthy", True))
                # 503 while the engine is stalled/broken (load
                # balancers pull the backend); the body still carries
                # the full stats so operators see WHY
                return self._json(
                    {"ok": ok, "model": model_name, **st},
                    200 if ok else 503,
                )
            if route == "/metrics":
                from mlcomp_tpu.obs.metrics import CONTENT_TYPE

                body = service.metrics.render().encode()
                self.send_response(200)
                self.send_header("Content-Type", CONTENT_TYPE)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return None
            if route == "/trace":
                from urllib.parse import parse_qs

                try:
                    qs = parse_qs(query)
                    last_ms = None
                    if qs.get("last_ms"):
                        last_ms = float(qs["last_ms"][0])
                        if last_ms <= 0:
                            raise ValueError(
                                f"last_ms must be positive, got {last_ms}"
                            )
                    trace_id = None
                    if qs.get("trace_id"):
                        trace_id = qs["trace_id"][0].strip().lower()
                        if not valid_trace_id(trace_id):
                            raise ValueError(
                                f"trace_id must be 32 hex chars, got "
                                f"{qs['trace_id'][0]!r}"
                            )
                    rid = None
                    if qs.get("rid"):
                        rid = int(qs["rid"][0])
                        if rid <= 0:
                            raise ValueError(
                                f"rid must be positive, got {rid}"
                            )
                    return self._json(service.trace(
                        last_ms=last_ms, trace_id=trace_id, rid=rid,
                    ))
                except ValueError as e:
                    return self._json(
                        {"error": f"{type(e).__name__}: {e}"}, 400
                    )
            if route == "/slo":
                try:
                    return self._json(service.slo_status())
                except ValueError as e:
                    # disabled sampler: absent surface
                    return self._json(
                        {"error": f"{type(e).__name__}: {e}"}, 404
                    )
            if route == "/metrics/history":
                from urllib.parse import parse_qs

                try:
                    qs = parse_qs(query)
                    window_s = None
                    if qs.get("window_s"):
                        window_s = float(qs["window_s"][0])
                        if window_s <= 0:
                            raise ValueError(
                                f"window_s must be positive, got "
                                f"{window_s}"
                            )
                except ValueError as e:
                    return self._json(
                        {"error": f"{type(e).__name__}: {e}"}, 400
                    )
                try:
                    return self._json(
                        service.metrics_history(window_s=window_s)
                    )
                except ValueError as e:
                    return self._json(
                        {"error": f"{type(e).__name__}: {e}"}, 404
                    )
            if route == "/profile":
                from urllib.parse import parse_qs

                try:
                    qs = parse_qs(query)
                    n = 8
                    if qs.get("dispatches"):
                        n = int(qs["dispatches"][0])
                    # tighter than the engine's own [1, 1024] cap: the
                    # close-of-window parse runs on the drive loop, so
                    # an HTTP caller gets a proportionate window only
                    if not 1 <= n <= 256:
                        raise ValueError(
                            f"dispatches must be in [1, 256], got {n}"
                        )
                except (ValueError, TypeError) as e:
                    return self._json(
                        {"error": f"{type(e).__name__}: {e}"}, 400
                    )
                try:
                    fut = service.profile(dispatches=n)
                except ProfileBusy as e:
                    return self._json(
                        {"error": str(e), "status": e.status}, 409,
                    )
                except Exception as e:
                    return self._json(
                        {"error": f"{type(e).__name__}: {e}"}, 500
                    )
                try:
                    # the capture is dispatch-gated: it needs live
                    # decode traffic to complete.  Same grace the
                    # generate path gives a wedged engine.
                    return self._json(
                        fut.result(
                            timeout=service.request_timeout_s + 30.0
                        )
                    )
                except FutTimeout:
                    service.profile_cancel(fut)
                    return self._json(
                        {"error": "capture did not complete (no decode "
                         "traffic inside the window?)",
                         "status": "profile_timeout"}, 504,
                    )
                except Exception as e:
                    return self._json(
                        {"error": f"{type(e).__name__}: {e}"}, 500
                    )
            if route == "/cache/stats":
                stats = service.cache_stats()
                if stats is None:
                    return self._json(
                        {"error": "prefix cache disabled "
                         "(start with --prefix-cache)"}, 404,
                    )
                return self._json(stats)
            return self._json({"error": "not found"}, 404)

        def _stream(self, fut, toks: "queue.Queue"):
            """Server-sent events: one ``data:`` line per token as it
            lands, a final ``done`` event with the full result, then
            close (Connection: close bounds the response body).

            Never raises: once the 200/event-stream headers are out, a
            failure must terminate the STREAM (an ``error`` event), not
            fall back to do_POST's JSON error path — that would write a
            second status line into the open body.  A broken pipe is
            client-disconnect detection: the request is CANCELLED at
            the engine so the row frees its slot at the next dispatch
            boundary instead of decoding for nobody."""
            # grace past the request timeout (every deadline clamps to
            # it): the engine fails the future at the deadline first,
            # so hitting THIS wait means the engine is unresponsive
            timeout = service.request_timeout_s + 30.0
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.send_header("Connection", "close")
            self.end_headers()
            try:
                while True:
                    item = toks.get(timeout=timeout)
                    if item is None:
                        break
                    self.wfile.write(
                        f"data: {json.dumps(item)}\n\n".encode()
                    )
                    self.wfile.flush()
                final = fut.result(timeout=timeout)
                self.wfile.write(
                    f"data: {json.dumps({'done': True, **final})}\n\n".encode()
                )
                self.wfile.flush()
            except ConnectionError:
                # client went away (broken pipe OR reset — curl Ctrl-C
                # and proxy teardown surface as RST): retire the row,
                # don't decode on
                service.cancel(getattr(fut, "rid", 0))
            except Exception as e:
                status = getattr(e, "status", None)
                err = json.dumps({
                    "error": f"{type(e).__name__}: {e}",
                    # the id is echoed on EVERY response path, and a
                    # failed stream is exactly where the client needs
                    # it to pull the request's spans from /trace
                    "trace_id": getattr(fut, "trace_id", None),
                    **({"status": status} if status else {}),
                })
                try:
                    self.wfile.write(f"data: {err}\n\n".encode())
                    self.wfile.flush()
                except OSError:
                    pass

        def _prefill(self, tid):
            """POST /prefill (phase=prefill replicas): run the
            admission core on a generate-shaped request and answer
            with the serialized KV-page handoff — the binary blob a
            decode replica's POST /import (or the phase-aware router)
            consumes.  Error semantics mirror /generate's."""
            if not service.engine.prefill_only:
                return self._json(
                    {"error": "this replica does not serve "
                     "phase=prefill; POST /generate instead",
                     "status": "wrong_phase", "trace_id": tid}, 409,
                    close=True,
                )
            try:
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n) or b"{}")
                fut = service.submit(
                    req["prompt"], int(req.get("max_new_tokens", 32)),
                    temperature=req.get("temperature"),
                    top_k=req.get("top_k"),
                    top_p=req.get("top_p"),
                    eos_id=req.get("eos_id"),
                    logprobs=req.get("logprobs", False),
                    repetition_penalty=req.get("repetition_penalty"),
                    deadline_s=req.get("deadline_s"),
                    trace_id=tid,
                )
                res = fut.result(
                    timeout=service.request_timeout_s + 30.0
                )
                blob = res.pop("handoff")
                self.send_response(200)
                self.send_header(
                    "Content-Type", "application/octet-stream"
                )
                self.send_header("Content-Length", str(len(blob)))
                # the sidecar summary (pages, cache hits, latency)
                # rides a header so the body stays the raw blob
                self.send_header("x-mlcomp-handoff", json.dumps(res))
                self.end_headers()
                self.wfile.write(blob)
                return None
            except BackpressureError as e:
                return self._reject_429(e, tid)
            except (DeadlineExceeded, FutTimeout) as e:
                return self._json(
                    {"error": f"{type(e).__name__}: {e}",
                     "status": "deadline_exceeded",
                     "trace_id": tid}, 504,
                )
            except (KeyError, ValueError, TypeError) as e:
                return self._json(
                    {"error": f"{type(e).__name__}: {e}",
                     "trace_id": tid}, 400,
                )
            except Exception as e:
                status = getattr(e, "status", None)
                return self._json(
                    {"error": f"{type(e).__name__}: {e}",
                     "trace_id": tid,
                     **({"status": status} if status else {})}, 500,
                )

        def _import(self, tid):
            """POST /import (paged replicas, usually phase=decode):
            admit a KV-page handoff blob.  ``?stream=1`` streams
            tokens over SSE exactly like /generate; a truncated or
            mismatched blob answers the typed 400 ``bad_handoff``
            with nothing allocated."""
            from mlcomp_tpu.kvpool.transfer import HandoffError

            try:
                n = int(self.headers.get("Content-Length", 0))
                blob = self.rfile.read(n)
                qs = self.path.partition("?")[2]
                want_stream = "stream=1" in qs or "stream=true" in qs
                toks: "queue.Queue" = (
                    queue.Queue() if want_stream else None
                )
                fut = service.import_pages(
                    blob, stream=toks, trace_id=tid,
                )
                if want_stream:
                    return self._stream(fut, toks)
                return self._json(
                    fut.result(timeout=service.request_timeout_s + 30.0)
                )
            except HandoffError as e:
                return self._json(
                    {"error": str(e), "status": e.status,
                     "trace_id": tid}, 400,
                )
            except BackpressureError as e:
                return self._reject_429(e, tid)
            except (DeadlineExceeded, FutTimeout) as e:
                return self._json(
                    {"error": f"{type(e).__name__}: {e}",
                     "status": "deadline_exceeded",
                     "trace_id": tid}, 504,
                )
            except (ValueError, TypeError) as e:
                return self._json(
                    {"error": f"{type(e).__name__}: {e}",
                     "trace_id": tid}, 400,
                )
            except Exception as e:
                status = getattr(e, "status", None)
                return self._json(
                    {"error": f"{type(e).__name__}: {e}",
                     "trace_id": tid,
                     **({"status": status} if status else {})}, 500,
                )

        def do_POST(self):  # noqa: N802
            if not self._token_ok():
                return self._json(
                    {"error": "invalid or missing token"}, 403,
                    close=True,
                )
            route = self.path.split("?", 1)[0]
            if route == "/drain":
                # the scale-down handshake (fleet/manager.py): flip
                # ready without touching ok, so routers stop sending
                # new work while in-flight requests finish.  Body
                # {"draining": false} un-drains.
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    req = json.loads(self.rfile.read(n) or b"{}")
                    draining = req.get("draining", True)
                    if not isinstance(draining, bool):
                        raise ValueError(
                            f"draining must be a JSON boolean, got "
                            f"{draining!r}"
                        )
                except (ValueError, TypeError) as e:
                    return self._json(
                        {"error": f"{type(e).__name__}: {e}"}, 400
                    )
                return self._json(
                    {"ok": True,
                     "draining": service.set_draining(draining)}
                )
            if route not in ("/generate", "/prefill", "/import"):
                return self._json(
                    {"error": "not found"}, 404, close=True,
                )
            # trace context: inherit the client's W3C ``traceparent``
            # trace id when one arrives well-formed, mint otherwise —
            # EVERY response path below (result, 4xx/5xx error bodies)
            # echoes the id, so a client can always hand it to
            # GET /trace?trace_id= (or the report server's fleet
            # merger) and pull this request's spans
            tid = parse_traceparent(self.headers.get("traceparent"))
            if tid is None:
                tid = make_trace_id()
            if route == "/prefill":
                return self._prefill(tid)
            if route == "/import":
                return self._import(tid)
            if service.phase == "prefill":
                # a prefill replica owns no decode loop: generation
                # belongs on a decode (or monolithic) replica — the
                # phase-aware router never lands here
                return self._json(
                    {"error": "this replica serves phase=prefill "
                     "(POST /prefill for a KV-page handoff); route "
                     "generation at a decode or monolithic replica",
                     "status": "wrong_phase", "trace_id": tid}, 409,
                    close=True,
                )
            try:
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n) or b"{}")
                prompt = req["prompt"]
                want_stream = bool(req.get("stream", False))
                toks: "queue.Queue" = queue.Queue() if want_stream else None
                fut = service.submit(
                    prompt, int(req.get("max_new_tokens", 32)),
                    temperature=req.get("temperature"),
                    top_k=req.get("top_k"),
                    top_p=req.get("top_p"),
                    eos_id=req.get("eos_id"),
                    logprobs=req.get("logprobs", False),
                    repetition_penalty=req.get("repetition_penalty"),
                    stream=toks,
                    deadline_s=req.get("deadline_s"),
                    trace_id=tid,
                )
                if want_stream:
                    return self._stream(fut, toks)
                # grace past the engine-enforced deadline (deadlines
                # clamp to the request timeout): the engine retires
                # the request and fails the future first, so this wait
                # resolving by TimeoutError means the engine itself is
                # unresponsive — also a gateway timeout
                return self._json(
                    fut.result(timeout=service.request_timeout_s + 30.0)
                )
            except BackpressureError as e:
                return self._reject_429(e, tid)
            except NotCoordinator as e:
                # a distributed follower: traffic belongs at the
                # coordinator — 503 + the body says where to look
                # (its /healthz already answers ready:false, so a
                # fleet router never lands here)
                return self._json(
                    {"error": str(e), "status": e.status,
                     "trace_id": tid}, 503,
                )
            except (DeadlineExceeded, FutTimeout) as e:
                return self._json(
                    {"error": f"{type(e).__name__}: {e}",
                     "status": "deadline_exceeded", "trace_id": tid}, 504,
                )
            except (KeyError, ValueError, TypeError) as e:
                return self._json(
                    {"error": f"{type(e).__name__}: {e}",
                     "trace_id": tid}, 400,
                )
            except Exception as e:
                status = getattr(e, "status", None)
                return self._json(
                    {"error": f"{type(e).__name__}: {e}", "trace_id": tid,
                     **({"status": status} if status else {})}, 500,
                )

    return ThreadingHTTPServer((host, port), Handler)


def serve_http(
    service: GenerationService,
    host: str = "127.0.0.1",
    port: int = 8900,
    model_name: str = "model",
):
    """Blocking HTTP front end (stdlib, threaded — handler threads wait
    on the engine's futures, which is exactly what gives concurrent
    requests a shared batch)."""
    httpd = make_http_server(service, host, port, model_name)
    print(json.dumps({
        "event": "serving", "host": host, "port": port,
        "model": model_name, **service.stats(),
    }), flush=True)
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        httpd.server_close()
        service.close()
