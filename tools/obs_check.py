#!/usr/bin/env python
"""Observability smoke harness: serve-path /metrics + /trace, checked.

Spins up a toy continuous engine behind the real serve daemon HTTP
stack (``serve.make_http_server`` on an ephemeral port, prefix cache
on), drives real requests through ``POST /generate``, then asserts the
observability contract the docs promise (docs/observability.md):

- ``GET /metrics`` parses as Prometheus text exposition: every sample
  line well-formed, every sample family preceded by exactly one
  ``# TYPE``, histogram ``_bucket`` series cumulative and capped by
  ``_count``;
- every DOCUMENTED serve-daemon metric is present (a metric renamed in
  code but not in docs — or vice versa — fails here, not in a user's
  dashboard);
- counters are MONOTONIC across two scrapes with traffic in between,
  and the traffic actually moved the request counter;
- ``GET /trace`` returns Chrome trace-event JSON (Perfetto-loadable):
  dispatch async begin/end pairs balance, issue/resolve spans exist,
  request lifecycle spans carry matched begin/ends, and ``last_ms``
  windowing returns a subset;
- ``GET /profile?dispatches=N`` completes against live traffic and
  returns the device-time attribution contract (device_time_ms,
  host_gap_ms, kernel breakdown, per-family roofline utilization),
  and the ``/trace`` fetched AFTER it carries the merged
  ``engine.device`` track aligned with the dispatch spans;
- the observability SPINE: ``GET /slo`` answers the default
  objectives' burn-rate/breach shape, ``GET /metrics/history`` serves
  the ring with non-negative (reset-clamped) counter deltas that sum
  to no more than the lifetime totals, a request that arrives with a
  W3C ``traceparent`` echoes its trace id and
  ``GET /trace?trace_id=`` / ``?rid=`` return exactly that request's
  events;
- the FLEET: a second toy daemon, adopted with the first into a
  two-replica set by the fleet ReplicaManager (mlcomp_tpu/fleet) and
  fronted by the prefix-affinity Router; a report server scraping the
  manager's DYNAMIC registry (``MLCOMP_TPU_SERVE_REGISTRY``) serves
  ONE merged ``/fleet/trace`` with one pid per daemon (named,
  clock-aligned) and one ``/fleet/metrics`` exposition with a
  ``daemon`` label per sample.  End to end through the router: a
  traced request's spans land under the replica that served it,
  a repeated prefix re-lands on its affinity replica and HITS its
  warmed cache (cache-hit-token counters prove it), every documented
  ``mlcomp_fleet_*`` family scrapes clean from the router's
  ``/metrics``, and the autoscaler's decision log responds to an
  injected burn-rate breach without moving the dry-run target.

No TPU needed (CPU jax), finishes in seconds; tests/test_obs_check.py
wires it into tier-1 like tools/cachecheck.py.  Standalone:

    python tools/obs_check.py
"""

from __future__ import annotations

import json
import os
import re
import sys
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the serve-daemon metric families docs/observability.md documents —
# keep the three in sync (this harness is the enforcement)
DOCUMENTED_SERVE_METRICS = [
    "mlcomp_engine_requests_total",
    "mlcomp_engine_dispatches_total",
    "mlcomp_engine_steps_total",
    "mlcomp_engine_emitted_tokens_total",
    "mlcomp_engine_prefills_total",
    "mlcomp_engine_prefill_chunks_total",
    "mlcomp_engine_fused_prefill_chunks_total",
    "mlcomp_engine_admissions_overlapped_total",
    "mlcomp_engine_inserts_behind_dispatch_total",
    "mlcomp_engine_admission_stall_ms",
    "mlcomp_engine_latency_samples_total",
    "mlcomp_engine_slots",
    "mlcomp_engine_active_slots",
    "mlcomp_engine_queue_depth",
    "mlcomp_engine_pipeline_depth",
    "mlcomp_engine_pipeline_inflight",
    "mlcomp_engine_pipeline_peak_inflight",
    "mlcomp_engine_pipeline_occupancy",
    "mlcomp_engine_pipeline_issued_total",
    "mlcomp_engine_pipeline_host_ms_total",
    "mlcomp_engine_pipeline_hidden_ms_total",
    "mlcomp_engine_pipeline_wait_ms_total",
    "mlcomp_engine_pipeline_overlap_efficiency",
    "mlcomp_engine_attention_rows_attended_total",
    "mlcomp_engine_attention_rows_total",
    "mlcomp_engine_attention_rows_starved_total",
    "mlcomp_engine_admission_lane_busy_ms_total",
    "mlcomp_engine_admission_blocked_ms_total",
    "mlcomp_engine_programs_compiled_total",
    "mlcomp_engine_programs_compile_seconds_total",
    "mlcomp_engine_attention_kv_rows_written_total",
    "mlcomp_engine_attention_kv_tokens_attended_total",
    "mlcomp_engine_attention_kv_tokens_live_total",
    "mlcomp_engine_attention_kv_tokens_fetched_total",
    "mlcomp_engine_attention_kv_trips_total",
    "mlcomp_engine_attention_kv_tokens_attended_window_total",
    "mlcomp_engine_attention_kv_tokens_live_window_total",
    "mlcomp_engine_dispatch_k",
    "mlcomp_engine_dispatch_k_changes_total",
    "mlcomp_engine_trace_events_dropped_total",
    "mlcomp_engine_ttft_ms",
    "mlcomp_engine_per_token_ms",
    "mlcomp_engine_device_time_ms",
    "mlcomp_engine_device_time_ms_per_dispatch",
    "mlcomp_engine_host_overhead_ms_per_dispatch",
    "mlcomp_engine_roofline_utilization",
    "mlcomp_engine_profile_captures_total",
    "mlcomp_engine_healthy",
    "mlcomp_engine_kv_pages_total",
    "mlcomp_engine_kv_pages_free",
    "mlcomp_engine_kv_pages_shared",
    "mlcomp_engine_kv_page_cow_forks_total",
    "mlcomp_engine_slots_scaled_total",
    "mlcomp_engine_live_slots",
    "mlcomp_engine_max_slots",
    "mlcomp_engine_kv_registry_hits_total",
    "mlcomp_engine_kv_registry_hit_tokens_total",
    "mlcomp_engine_kv_bytes_moved_per_dispatch",
    "mlcomp_engine_kv_pages_lazy_allocated_total",
    "mlcomp_engine_kv_decode_page_failures_total",
    "mlcomp_engine_handoffs_imported_total",
    "mlcomp_engine_kv_pages_imported_total",
    "mlcomp_engine_handoff_bytes_imported_total",
    "mlcomp_engine_handoff_rejects_total",
    "mlcomp_engine_deadline_exceeded_total",
    "mlcomp_engine_cancelled_total",
    "mlcomp_engine_watchdog_stalls_total",
    "mlcomp_engine_watchdog_restarts_total",
    "mlcomp_cache_degraded_total",
    "mlcomp_serving_requests_rejected_total",
    "mlcomp_service_info",
    "mlcomp_prefix_cache_lookups_total",
    "mlcomp_prefix_cache_hits_total",
    "mlcomp_prefix_cache_misses_total",
    "mlcomp_prefix_cache_matched_tokens_total",
    "mlcomp_prefix_cache_used_hits_total",
    "mlcomp_prefix_cache_used_hit_tokens_total",
    "mlcomp_prefix_cache_inserted_tokens_total",
    "mlcomp_prefix_cache_evictions_total",
    "mlcomp_prefix_cache_evicted_tokens_total",
    "mlcomp_prefix_cache_insert_errors_total",
    "mlcomp_prefix_cache_insert_dropped_total",
    "mlcomp_prefix_cache_bytes",
    "mlcomp_prefix_cache_max_bytes",
    "mlcomp_prefix_cache_nodes",
    "mlcomp_prefix_cache_pinned_nodes",
    "mlcomp_prefix_cache_outstanding_leases",
    "mlcomp_prefix_cache_capture_queue_depth",
    "mlcomp_metrics_history_samples_total",
    "mlcomp_metrics_history_span_seconds",
    "mlcomp_slo_burn_rate",
    "mlcomp_slo_breached",
    "mlcomp_slo_breaches_total",
]

# the fleet control-plane families docs/observability.md documents
# (rendered by the ROUTER's /metrics — manager, router, and autoscaler
# share one registry); graftcheck's drift pass keeps this list, the
# docs catalog, and the mlcomp_tpu/fleet/ collectors in three-way sync
DOCUMENTED_FLEET_METRICS = [
    "mlcomp_fleet_replicas_target",
    "mlcomp_fleet_replicas_live",
    "mlcomp_fleet_replica_restarts_total",
    "mlcomp_fleet_router_requests_total",
    "mlcomp_fleet_router_routed_total",
    "mlcomp_fleet_router_upstream_retries_total",
    "mlcomp_fleet_router_replicas_live",
    "mlcomp_fleet_autoscale_decisions_total",
    "mlcomp_fleet_replicas_live_by_phase",
    "mlcomp_fleet_router_handoffs_total",
    "mlcomp_fleet_router_handoff_failures_total",
    "mlcomp_fleet_router_handoff_bytes_total",
    "mlcomp_fleet_router_handoff_ms",
    "mlcomp_fleet_router_conn_opens_total",
    "mlcomp_fleet_router_conn_reuses_total",
]

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? (-?[0-9.eE+\-]+|\+Inf|NaN)$"
)
_LABELS_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_exposition(text: str):
    """Lint + parse Prometheus text format.  Returns
    ``(samples, types)``: ``samples`` maps sample name (including
    ``_bucket``/``_sum``/``_count`` suffixes) -> {labelstring: value},
    ``types`` maps family name -> type.  Raises AssertionError on any
    malformed line or a sample without a preceding # TYPE."""
    samples: dict = {}
    types: dict = {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            continue
        if line.startswith("# TYPE "):
            parts = line.split(" ")
            assert len(parts) == 4, f"malformed TYPE line: {line!r}"
            name, kind = parts[2], parts[3]
            assert kind in ("counter", "gauge", "histogram", "untyped"), line
            assert name not in types, f"duplicate TYPE for {name}"
            types[name] = kind
            continue
        assert not line.startswith("#"), f"unknown comment: {line!r}"
        m = _SAMPLE_RE.match(line)
        assert m, f"malformed sample line: {line!r}"
        name, labels, value = m.group(1), m.group(2) or "", m.group(3)
        family = re.sub(r"_(bucket|sum|count)$", "", name)
        assert name in types or family in types, (
            f"sample {name} has no # TYPE"
        )
        if labels:
            body = labels[1:-1]
            rebuilt = ",".join(
                f'{k}="{v}"' for k, v in _LABELS_RE.findall(body)
            )
            assert rebuilt == body, f"malformed labels: {labels!r}"
        v = float(value.replace("+Inf", "inf"))
        samples.setdefault(name, {})[labels] = v
    return samples, types


def check_histograms(samples, types):
    """Cumulative-bucket sanity for every histogram family."""
    for family, kind in types.items():
        if kind != "histogram":
            continue
        buckets = samples.get(f"{family}_bucket", {})
        counts = samples.get(f"{family}_count", {})
        assert buckets and counts, f"{family}: empty histogram"
        # group bucket series by their non-le labels
        by_group: dict = {}
        for labels, v in buckets.items():
            body = labels[1:-1] if labels else ""
            pairs = dict(_LABELS_RE.findall(body))
            le = pairs.pop("le")
            key = tuple(sorted(pairs.items()))
            by_group.setdefault(key, []).append((le, v))
        for key, series in by_group.items():
            inf = [v for le, v in series if le == "+Inf"]
            assert inf, f"{family}{key}: no +Inf bucket"
            finite = sorted(
                ((float(le), v) for le, v in series if le != "+Inf")
            )
            last = 0.0
            for _, v in finite:
                assert v >= last, f"{family}{key}: non-cumulative buckets"
                last = v
            assert inf[0] >= last, f"{family}{key}: +Inf below last bucket"


def _counters_monotonic(before, after, types):
    for family, kind in types.items():
        if kind != "counter":
            continue
        for labels, v0 in before.get(family, {}).items():
            v1 = after.get(family, {}).get(labels)
            assert v1 is not None, f"counter {family}{labels} vanished"
            assert v1 >= v0, (
                f"counter {family}{labels} went backwards: {v0} -> {v1}"
            )


def run(n_requests: int = 3) -> dict:
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from mlcomp_tpu.models import create_model
    from mlcomp_tpu.serve import GenerationService, make_http_server
    from mlcomp_tpu.train.state import init_model

    model = create_model({
        "name": "transformer_lm", "vocab_size": 64, "hidden": 32,
        "layers": 1, "heads": 2, "mlp_dim": 64, "dtype": "float32",
    })
    prompt = jnp.asarray(np.random.RandomState(0).randint(1, 64, (1, 8)))
    params, _ = init_model(model, {"x": prompt}, jax.random.PRNGKey(0))
    # prefill_chunk 8 divides the 16 bucket, so the prefix cache's hit
    # path (and its metrics) can actually engage on repeated prompts;
    # the PAGED KV layout (kvpool) runs live so its gauge/counter
    # families — pool occupancy, COW forks, elastic slot scaling, the
    # device prefix registry — are asserted against real traffic too
    svc = GenerationService(
        model, {"params": params}, batch_sizes=(1, 2),
        prompt_buckets=(16,), max_new_buckets=(8,),
        prefix_cache=True, prefill_chunk=8,
        kv_layout="paged", max_slots=4, kv_pages=2 + 64,
        # a fast history cadence so the spine surfaces (/slo,
        # /metrics/history, the mlcomp_slo_*/history families) carry
        # real samples within this harness's lifetime
        metrics_history_interval=0.25,
    )
    httpd = make_http_server(svc, "127.0.0.1", 0, "obs-check")
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    port = httpd.server_address[1]
    base = f"http://127.0.0.1:{port}"

    def generate(ids, max_new=4, headers=None, at=None):
        body = json.dumps(
            {"prompt": ids, "max_new_tokens": max_new}
        ).encode()
        req = urllib.request.Request(
            f"{at or base}/generate", data=body,
            headers={"Content-Type": "application/json",
                     **(headers or {})},
        )
        with urllib.request.urlopen(req, timeout=600) as r:
            return json.loads(r.read())

    def get(path, at=None):
        with urllib.request.urlopen(
            f"{at or base}{path}", timeout=60
        ) as r:
            return r.read()

    try:
        shared = [9, 10, 11, 12, 13, 14, 15, 16, 17]
        for i in range(n_requests):
            out = generate(shared + [i + 1])
            assert len(out["ids"]) == 4, out
        svc.prefix_cache.flush()

        # device-profile capture BEFORE the first scrape: the capture
        # feeds mlcomp_engine_device_time_ms and flips the roofline
        # gauges to capture-sourced, so the documented-metric check
        # below sees every family.  The window is dispatch-gated, so
        # traffic must flow while the request waits — pump generates
        # until it resolves.
        prof_res: dict = {}

        def _arm_profile():
            try:
                with urllib.request.urlopen(
                    f"{base}/profile?dispatches=2", timeout=300
                ) as r:
                    prof_res["code"] = r.status
                    prof_res["body"] = json.loads(r.read())
            except Exception as e:
                prof_res["error"] = repr(e)

        th = threading.Thread(target=_arm_profile, daemon=True)
        th.start()
        pumped = 0
        while th.is_alive() and pumped < 64:
            generate(shared + [50 + pumped])
            pumped += 1
        th.join(timeout=120)
        assert prof_res.get("code") == 200, prof_res
        att = prof_res["body"]
        for key in ("dispatches", "device_time_ms", "host_gap_ms",
                    "device_time_ms_per_dispatch", "kernels", "families",
                    "roofline_ms_per_dispatch", "roofline_utilization"):
            assert key in att, f"/profile missing {key!r}: {sorted(att)}"
        assert att["dispatches"] >= 1
        assert att["device_time_ms"] > 0
        assert att["kernels"] and att["families"]
        for fam in att["families"].values():
            for key in ("dispatches", "device_time_ms", "host_gap_ms",
                        "roofline_utilization"):
                assert key in fam, fam
        # one capture at a time: a second request while nothing is
        # armed must NOT 409 (the slot freed) — but arming twice does.
        # (the live 409 is covered by tests/test_serve.py; here we just
        # assert the slot is free again)
        assert svc.engine._profile is None

        # a deterministic history sample before the first scrape: the
        # SLO gauges and history families materialize at the first
        # sampler tick, and the documented-metric check below must see
        # every family
        svc.history.sample_now()
        text1 = get("/metrics").decode()
        s1, t1 = parse_exposition(text1)
        check_histograms(s1, t1)
        missing = [
            m for m in DOCUMENTED_SERVE_METRICS
            if m not in t1
        ]
        assert not missing, f"documented metrics absent: {missing}"
        req0 = s1["mlcomp_engine_requests_total"][""]

        for i in range(n_requests):
            generate(shared + [100 + i])
            # a different LENGTH: same prefix at a different placement
            # misses the placement-exact device registry and exercises
            # the HOST prefix-cache tier (token-indexed, re-placed)
            generate(shared + [100 + i, 7])
        # FULL-budget decodes: max_new 8 pushes the write span past
        # the insert's one-dispatch lookahead, so the fused paged
        # engine allocates its last decode page LAZILY mid-stream —
        # the counter asserted below
        for i in range(2):
            out = generate(shared + [200 + i], max_new=8)
            assert len(out["ids"]) == 8, out
        text2 = get("/metrics").decode()
        s2, t2 = parse_exposition(text2)
        check_histograms(s2, t2)
        _counters_monotonic(s1, s2, t1)
        req1 = s2["mlcomp_engine_requests_total"][""]
        assert req1 == req0 + 2 * n_requests + 2, (req0, req1)
        assert s2["mlcomp_prefix_cache_hits_total"][""] > 0
        # paged-KV pool gauges carry live occupancy, and the device
        # registry tier absorbed the same-placement repeats
        kv_total = s2["mlcomp_engine_kv_pages_total"][""]
        kv_free = s2["mlcomp_engine_kv_pages_free"][""]
        assert kv_total > 0 and 0 <= kv_free <= kv_total
        assert s2["mlcomp_engine_kv_registry_hits_total"][""] > 0
        assert s2["mlcomp_engine_live_slots"][""] >= 1
        # fused paged attention (the daemon's default data path):
        # the bytes-moved gauge is live, the full-budget decodes above
        # allocated decode pages lazily, and nothing starved
        assert s2["mlcomp_engine_kv_bytes_moved_per_dispatch"][""] >= 0
        assert s2["mlcomp_engine_kv_pages_lazy_allocated_total"][""] > 0
        assert s2["mlcomp_engine_kv_decode_page_failures_total"][""] == 0

        # ---- adaptive dispatch depth: the daemon runs the serve
        # default (steps_per_dispatch="adaptive"), so the dispatch_k
        # gauge must sit on the ladder — and a CONCURRENT burst (queue
        # deeper than the slot pool) must move the controller off the
        # quiesce floor: the changes counter advances and the gauge
        # still reads a ladder rung afterwards
        assert svc.engine.adaptive_k, "serve default should be adaptive"
        ladder = set(svc.engine.k_ladder)
        assert s2["mlcomp_engine_dispatch_k"][""] in ladder, (
            s2["mlcomp_engine_dispatch_k"], ladder
        )
        changes0 = s2["mlcomp_engine_dispatch_k_changes_total"][""]
        # distinct in-vocab tails (vocab_size=64: an out-of-range id
        # would clamp in the embedding gather and collapse the burst
        # into 8 copies of one prompt)
        burst_threads = [
            threading.Thread(
                target=lambda i=i: generate(shared + [40 + i],
                                            max_new=8),
                daemon=True,
            )
            for i in range(8)
        ]
        for th2 in burst_threads:
            th2.start()
        for th2 in burst_threads:
            th2.join(timeout=300)
        s2b, t2b = parse_exposition(get("/metrics").decode())
        assert s2b["mlcomp_engine_dispatch_k"][""] in ladder
        assert (
            s2b["mlcomp_engine_dispatch_k_changes_total"][""] > changes0
        ), "adaptive-K gauge never moved under the burst"

        # the last request resolves one boundary before the loop reads
        # the dispatch still in flight behind it: let the pipeline
        # empty, or the export holds a dispatch span not yet closed
        for _ in range(200):
            if not json.loads(get("/healthz"))["engine"]["pipeline"][
                    "inflight"]:
                break
            time.sleep(0.01)
        trace = json.loads(get("/trace?last_ms=600000"))
        evs = trace["traceEvents"]
        assert isinstance(evs, list) and evs, "empty trace"
        for e in evs:
            assert "ph" in e and "pid" in e, e
        begins = sum(
            1 for e in evs if e["ph"] == "b" and e["name"] == "dispatch"
        )
        ends = sum(
            1 for e in evs if e["ph"] == "e" and e["name"] == "dispatch"
        )
        assert begins and begins == ends, (begins, ends)
        names = {e["name"] for e in evs}
        for want in ("boundary", "maintenance", "admission_tick",
                     "issue", "resolve", "unpack", "admission_start",
                     "admission_complete",
                     "request", "admit", "inserted", "first_token",
                     "prefill_chunk", "insert", "prefix_cache.lookup",
                     "kv_registry.lookup", "clock_sync",
                     "admission", "compile"):
            assert want in names, f"missing trace span {want!r}"
        # the /profile capture merged a DEVICE track: a named
        # engine.device thread whose complete spans sit inside the
        # capture window — host spans render aligned above them
        track_tids = {
            e["args"]["name"]: e["tid"] for e in evs
            if e["ph"] == "M" and e["name"] == "thread_name"
        }
        assert "engine.device" in track_tids, sorted(track_tids)
        # the admission lane's own row: one span an admission, never
        # two at once, each naming the request span it belongs to; and
        # every ``admit`` says what its queue wait was booked under
        lane = sorted(
            (e for e in evs if e.get("tid") == track_tids["engine.lane"]
             and e["ph"] == "X"), key=lambda e: e["ts"],
        )
        assert lane and all(e["name"] == "admission" for e in lane)
        for a, b in zip(lane, lane[1:]):
            assert a["ts"] + a["dur"] <= b["ts"], (a, b)
        assert all(
            e["args"]["boundaries"] >= 1 and "caused_by" in e["args"]
            for e in lane
        )
        for e in evs:
            if e["name"] == "admit":
                assert set(e["args"]["blocked_ms"]) == {
                    "lane", "slot", "pages"}, e
        assert "engine.compile" in track_tids, sorted(track_tids)
        dev_evs = [
            e for e in evs
            if e.get("tid") == track_tids["engine.device"]
            and e["ph"] == "X"
        ]
        assert dev_evs, "device track carries no spans"
        for e in dev_evs:
            assert e.get("dur", 0) >= 0 and "ts" in e, e
        # alignment: the device spans overlap the host dispatch span
        # range (both sit on the recorder clock)
        disp_ts = [
            e["ts"] for e in evs
            if e.get("cat") == "disp" and e["ph"] in ("b", "e")
        ]
        dev_lo = min(e["ts"] for e in dev_evs)
        dev_hi = max(e["ts"] + e.get("dur", 0) for e in dev_evs)
        assert disp_ts and dev_lo <= max(disp_ts) and (
            dev_hi >= min(disp_ts)
        ), "device track does not overlap the dispatch spans"
        assert "device_capture" in names
        # last_ms windows: a zero-width trailing window drops the
        # decode-time events the full fetch carried
        tiny = json.loads(get("/trace?last_ms=0.001"))
        assert len(tiny["traceEvents"]) <= len(evs)

        # ---- observability spine: /slo against the default objectives
        slo = json.loads(get("/slo"))
        assert slo["evaluations"] >= 1, slo
        assert set(slo["slos"]) == {
            "ttft_p95", "per_token_p50", "reject_rate", "engine_healthy"
        }, sorted(slo["slos"])
        for name, st in slo["slos"].items():
            assert set(st["burn_rate"]) == {"fast", "slow"}, (name, st)
            assert all(v >= 0 for v in st["burn_rate"].values()), st
            assert isinstance(st["breached"], bool), st
        # nothing was rejected and the engine never went unhealthy:
        # those objectives cannot be burning.  The toy LATENCY SLOs may
        # legitimately breach (first-request compile TTFT blows a 2 s
        # objective) — that is the burn math working, not a failure.
        for name in ("reject_rate", "engine_healthy"):
            assert not slo["slos"][name]["breached"], slo["slos"][name]
        assert set(slo["breached"]) <= {"ttft_p95", "per_token_p50"}
        hz = json.loads(get("/healthz"))
        assert hz["slo"]["breached"] == slo["breached"], hz["slo"]
        assert hz["metrics_history"]["samples_taken"] >= 1

        # ---- /metrics/history: reset-clamped deltas vs lifetime totals
        svc.history.sample_now()  # tail sample carrying today's traffic
        hist = json.loads(get("/metrics/history?window_s=600"))
        assert hist["samples"], hist
        key = "mlcomp_engine_requests_total"
        deltas = [s["counters"].get(key, 0.0) for s in hist["samples"]]
        assert all(d >= 0 for d in deltas), deltas
        assert 0 < sum(deltas) <= hist["totals"][key], (
            deltas, hist["totals"].get(key)
        )
        assert any(
            (s["quantiles"].get("mlcomp_engine_ttft_ms") or {}).get("p50")
            is not None
            for s in hist["samples"]
        ), "no materialized TTFT quantile in any window sample"

        # ---- trace-id propagation: inherit a traceparent, echo it,
        #      filter the flight recorder down to that one request
        tid = "0af7651916cd43dd8448eb211c80319c"
        out = generate(shared + [240], headers={
            "traceparent": f"00-{tid}-00f067aa0ba902b7-01",
        })
        assert out["trace_id"] == tid, out
        filt = json.loads(get(f"/trace?trace_id={tid}"))
        rids = filt["otherData"]["filter"]["rids"]
        assert len(rids) == 1, rids
        rid = rids[0]
        non_meta = [e for e in filt["traceEvents"] if e["ph"] != "M"]
        assert non_meta, "trace-id filter returned nothing"
        for e in non_meta:
            args = e.get("args") or {}
            assert (
                (e.get("cat") == "req" and e.get("id") == str(rid))
                or args.get("rid") == rid
                or args.get("trace_id") == tid
            ), e
        fnames = {e["name"] for e in non_meta}
        assert {"request", "insert"} <= fnames, sorted(fnames)
        by_rid = json.loads(get(f"/trace?rid={rid}"))
        assert len(by_rid["traceEvents"]) == len(filt["traceEvents"])

        # ---- disaggregation: a prefill service exports a KV-page
        #      handoff, the MAIN (paged) daemon imports it via POST
        #      /import, and both sides' handoff metric families carry
        #      the traffic (docs/observability.md catalog rows)
        pre_svc = GenerationService(
            model, {"params": params}, batch_sizes=(1, 2),
            prompt_buckets=(16,), max_new_buckets=(8,),
            prefill_chunk=8, phase="prefill",
        )
        pre_httpd = make_http_server(
            pre_svc, "127.0.0.1", 0, "obs-prefill"
        )
        threading.Thread(
            target=pre_httpd.serve_forever, daemon=True
        ).start()
        pre_base = f"http://127.0.0.1:{pre_httpd.server_address[1]}"
        try:
            body = json.dumps({
                "prompt": shared + [77], "max_new_tokens": 4,
            }).encode()
            req = urllib.request.Request(
                f"{pre_base}/prefill", data=body,
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=600) as r:
                blob = r.read()
                assert r.headers["Content-Type"] == (
                    "application/octet-stream"
                )
            req = urllib.request.Request(
                f"{base}/import", data=blob,
                headers={"Content-Type": "application/octet-stream"},
            )
            with urllib.request.urlopen(req, timeout=600) as r:
                imp = json.loads(r.read())
            assert len(imp["ids"]) == 4, imp
            # a truncated blob rejects typed — and is COUNTED
            req = urllib.request.Request(
                f"{base}/import", data=blob[: len(blob) // 2],
                headers={"Content-Type": "application/octet-stream"},
            )
            try:
                urllib.request.urlopen(req, timeout=600)
                raise AssertionError("partial import accepted")
            except urllib.error.HTTPError as e:
                assert e.code == 400, e.code
                assert json.loads(e.read())["status"] == "bad_handoff"
            ds, dt = parse_exposition(get("/metrics").decode())
            for fam, least in (
                ("mlcomp_engine_handoffs_imported_total", 1),
                ("mlcomp_engine_kv_pages_imported_total", 1),
                ("mlcomp_engine_handoff_bytes_imported_total", 1),
                ("mlcomp_engine_handoff_rejects_total", 1),
            ):
                assert ds[fam][""] >= least, (fam, ds.get(fam))
            es, et = parse_exposition(
                get("/metrics", at=pre_base).decode()
            )
            for fam in (
                "mlcomp_engine_handoffs_exported_total",
                "mlcomp_engine_kv_pages_exported_total",
                "mlcomp_engine_handoff_bytes_exported_total",
            ):
                assert es[fam][""] >= 1, (fam, es.get(fam))
            hz_pre = json.loads(get("/healthz", at=pre_base))
            assert hz_pre["phase"] == "prefill", hz_pre
            disagg_imports = int(
                ds["mlcomp_engine_handoffs_imported_total"][""]
            )
        finally:
            pre_httpd.shutdown()
            pre_httpd.server_close()
            pre_svc.close()

        # ---- the fleet: a second daemon behind a managed router +
        #      a report server scraping the DYNAMIC registry -> one
        #      merged Perfetto trace, one labeled exposition, affinity
        #      verified by cache-hit counters, autoscaler decision log
        import tempfile
        from types import SimpleNamespace

        from mlcomp_tpu.fleet import (
            Autoscaler,
            AutoscalePolicy,
            CallableLauncher,
            ReplicaManager,
            ReplicaSpec,
            Router,
            make_router_http_server,
        )
        from mlcomp_tpu.obs.metrics import Registry as ObsRegistry
        from mlcomp_tpu.report.server import start_in_thread

        svc2 = GenerationService(
            model, {"params": params}, batch_sizes=(1,),
            prompt_buckets=(16,), max_new_buckets=(8,),
            prefix_cache=True, prefill_chunk=8,
            metrics_history_interval=0,
        )
        httpd2 = make_http_server(svc2, "127.0.0.1", 0, "obs-check-2")
        threading.Thread(
            target=httpd2.serve_forever, daemon=True
        ).start()
        base2 = f"http://127.0.0.1:{httpd2.server_address[1]}"
        saved_env = {
            k: os.environ.get(k)
            for k in ("MLCOMP_TPU_SERVE_URLS", "MLCOMP_TPU_SERVE_URL",
                      "MLCOMP_TPU_SERVE_REGISTRY")
        }
        report_srv = None
        mgr = router = rhttpd = None
        try:
            generate([3, 4, 5, 6], at=base2)
            # the manager adopts both daemons as a two-replica set and
            # publishes them into the JSON registry the report server
            # reads (MLCOMP_TPU_SERVE_URLS' dynamic successor; the env
            # var remains the static fallback)
            reg_path = tempfile.mktemp(suffix=".json")
            fleet_urls = {"fleet-0": base, "fleet-1": base2}
            fleet_svcs = {"fleet-0": svc, "fleet-1": svc2}
            fleet_reg = ObsRegistry()
            mgr = ReplicaManager(
                CallableLauncher(lambda name, port: SimpleNamespace(
                    url=fleet_urls[name], stop=lambda: None,
                )),
                ReplicaSpec(target=2, health_poll_s=0.2),
                metrics=fleet_reg, registry_path=reg_path,
            )
            mgr.tick()
            assert mgr.stats()["live"] == 2, mgr.stats()
            router = Router(manager=mgr, metrics=fleet_reg,
                            health_poll_s=0.2)
            router.poll_once()
            scaler = Autoscaler(
                AutoscalePolicy(min_replicas=1, max_replicas=4,
                                sustain_s=0.0, cooldown_s=0.0),
                manager=mgr, metrics=fleet_reg, dry_run=True,
            )
            rhttpd = make_router_http_server(router, "127.0.0.1", 0)
            threading.Thread(
                target=rhttpd.serve_forever, daemon=True
            ).start()
            rrbase = f"http://127.0.0.1:{rhttpd.server_address[1]}"
            os.environ.pop("MLCOMP_TPU_SERVE_URLS", None)
            os.environ["MLCOMP_TPU_SERVE_REGISTRY"] = reg_path
            report_srv, rport = start_in_thread(
                tempfile.mktemp(suffix=".sqlite")
            )
            rbase = f"http://127.0.0.1:{rport}"
            fleet = json.loads(get("/fleet/trace", at=rbase))
            fevs = fleet["traceEvents"]
            pids = {e["pid"] for e in fevs}
            assert pids == {1, 2}, pids  # one pid per daemon
            pnames = {
                e["pid"]: e["args"]["name"] for e in fevs
                if e["ph"] == "M" and e["name"] == "process_name"
            }
            assert len(pnames) == 2, pnames
            for pid in (1, 2):
                assert any(
                    e["pid"] == pid and e["name"] == "issue"
                    for e in fevs
                ), f"daemon pid {pid} contributed no issue span"
            # alignment: both daemons' events land on ONE clock —
            # non-negative, and spanning no more than this harness's
            # real lifetime (an unaligned epoch would be hours off)
            ts = [e["ts"] for e in fevs if "ts" in e]
            assert min(ts) >= 0 and max(ts) < 3600e6, (
                min(ts), max(ts)
            )
            # the trace id minted on daemon 1 filters the WHOLE
            # fleet's merged view down to that daemon's request
            ffilt = json.loads(
                get(f"/fleet/trace?trace_id={tid}", at=rbase)
            )
            fnm = [
                e for e in ffilt["traceEvents"] if e["ph"] != "M"
            ]
            assert fnm and all(e["pid"] == 1 for e in fnm), fnm
            ftext = get("/fleet/metrics", at=rbase).decode()
            fs, ft = parse_exposition(ftext)
            req_rows = fs["mlcomp_engine_requests_total"]
            assert len(req_rows) == 2, req_rows  # one per daemon label
            assert all("daemon=" in k for k in req_rows), req_rows
            ups = fs["mlcomp_fleet_daemon_up"]
            assert sorted(ups.values()) == [1.0, 1.0], ups

            # ---- the router end to end: a traced request lands in
            #      /fleet/trace under the REPLICA that served it
            def via_router(ids, headers=None):
                body = json.dumps(
                    {"prompt": ids, "max_new_tokens": 4}
                ).encode()
                req = urllib.request.Request(
                    f"{rrbase}/generate", data=body,
                    headers={"Content-Type": "application/json",
                             **(headers or {})},
                )
                with urllib.request.urlopen(req, timeout=600) as r:
                    return (
                        json.loads(r.read()),
                        r.headers.get("x-mlcomp-replica"),
                    )
            tid3 = "1bad5eed5eed5eed5eed5eed5eed5eed"
            out3, served_by = via_router(shared + [91], headers={
                "traceparent": f"00-{tid3}-00f067aa0ba902b7-01",
            })
            assert out3["trace_id"] == tid3, out3
            assert served_by in fleet_urls, served_by
            # the replica's daemon name -> its pid in the merged view
            daemon3 = fleet_urls[served_by].split("://", 1)[-1]
            served_pid = {v: k for k, v in pnames.items()}[daemon3]
            f3 = json.loads(
                get(f"/fleet/trace?trace_id={tid3}", at=rbase)
            )
            f3nm = [e for e in f3["traceEvents"] if e["ph"] != "M"]
            assert f3nm, "router-traced request left no fleet spans"
            assert all(e["pid"] == served_pid for e in f3nm), (
                served_pid, f3nm[:3],
            )

            # ---- affinity: the SAME prefix re-lands on the same
            #      replica and hits its warmed cache (cache-hit-token
            #      counters are the proof)
            p_aff = shared + [92]
            _, first_rep = via_router(p_aff)
            fleet_svcs[first_rep].prefix_cache.flush()
            out_rep, again_rep = via_router(p_aff)
            assert again_rep == first_rep, (first_rep, again_rep)
            assert out_rep.get("cache_hit_tokens", 0) > 0, out_rep
            rst = router.status()
            assert rst["counts"]["reason"]["affinity"] >= 1, rst

            # ---- the new metric families scrape clean from the
            #      router's shared fleet registry
            ftext2 = get("/metrics", at=rrbase).decode()
            fs2, ft2 = parse_exposition(ftext2)
            missing = [
                m for m in DOCUMENTED_FLEET_METRICS if m not in ft2
            ]
            assert not missing, f"fleet metrics absent: {missing}"
            assert fs2["mlcomp_fleet_replicas_live"][""] == 2, fs2
            ok_reqs = fs2["mlcomp_fleet_router_requests_total"][
                '{outcome="ok"}'
            ]
            assert ok_reqs >= 3, fs2["mlcomp_fleet_router_requests_total"]

            # ---- autoscaler: the decision log responds to an
            #      injected burn-rate breach (dry-run: logged and
            #      counted, target untouched)
            from mlcomp_tpu.fleet.autoscale import FleetSignals

            live_decision = scaler.run_tick(urls=list(
                fleet_urls.values()
            ))
            assert live_decision["signals"]["live_replicas"] == 2, (
                live_decision
            )
            breach = scaler.observe(FleetSignals(
                slo_breached=True, requests_delta=10, live_replicas=2,
            ))
            assert breach["direction"] == "up", breach
            assert breach["reason"] == "slo_burn", breach
            assert breach["dry_run"] and not breach["applied"], breach
            assert mgr.stats()["target"] == 2  # dry run never applies
            ftext3 = get("/metrics", at=rrbase).decode()
            fs3, _ = parse_exposition(ftext3)
            ups_dec = fs3["mlcomp_fleet_autoscale_decisions_total"][
                '{direction="up"}'
            ]
            assert ups_dec >= 1, fs3
        finally:
            for k, v in saved_env.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            if rhttpd is not None:
                rhttpd.shutdown()
                rhttpd.server_close()
            if router is not None:
                router.close()
            if mgr is not None:
                mgr.close(stop_replicas=False)
            if report_srv is not None:
                report_srv.shutdown()
                report_srv.server_close()
            httpd2.shutdown()
            httpd2.server_close()
            svc2.close()

        return {
            "requests": int(req1),
            "metric_families": len(t2),
            "trace_events": len(evs),
            "dispatch_spans": begins,
            "profile_dispatches": int(att["dispatches"]),
            "device_track_spans": len(dev_evs),
            "device_time_ms": att["device_time_ms"],
            "slo_evaluations": int(slo["evaluations"]),
            "history_samples": len(hist["samples"]),
            "trace_filter_events": len(non_meta),
            "fleet_daemons": len(pnames),
            "fleet_trace_events": len(fevs),
            "router_requests_ok": int(ok_reqs),
            "router_affinity_routes": int(
                rst["counts"]["reason"]["affinity"]
            ),
            "autoscale_decision": breach["direction"],
            "disagg_handoffs_imported": disagg_imports,
        }
    finally:
        httpd.shutdown()
        httpd.server_close()
        svc.close()


def main(argv=None) -> int:
    out = run()
    print(f"ok: {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
