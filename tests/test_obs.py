"""Observability layer: metrics registry exposition lint, tracer ring
buffer + windowed export, multithreaded save/append safety, the serve
daemon's /metrics + /trace surfaces, and the report server's /metrics
aggregation."""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from mlcomp_tpu.obs.metrics import (
    CONTENT_TYPE,
    Registry,
    default_registry,
)
from mlcomp_tpu.utils.trace import Tracer, null_tracer


# ----------------------------------------------------------- metrics unit


def test_counter_gauge_exposition_and_types():
    reg = Registry()
    c = reg.counter("x_total", "things")
    c.inc()
    c.inc(2)
    g = reg.gauge("depth", "queue depth")
    g.set(3)
    g.dec()
    text = reg.render()
    assert "# HELP x_total things" in text
    assert "# TYPE x_total counter" in text
    assert "\nx_total 3\n" in text
    assert "# TYPE depth gauge" in text
    assert "\ndepth 2" in text
    with pytest.raises(ValueError):
        c.inc(-1)  # counters cannot decrease
    with pytest.raises(ValueError):
        reg.gauge("x_total", "type clash")  # name registered as counter
    assert reg.counter("x_total", "same family") is c  # create-or-get


def test_counter_set_total_is_monotonic():
    reg = Registry()
    c = reg.counter("snap_total", "snapshot-sourced")
    c.set_total(10)
    c.set_total(7)  # racing stale snapshot: clamped, never backwards
    assert c.value() == 10
    c.set_total(12)
    assert c.value() == 12


def test_label_escaping_and_schema():
    reg = Registry()
    g = reg.gauge("lbl", "labelled", labelnames=("name",))
    g.set(1, name='we"ird\\path\nline')
    line = [
        ln for ln in reg.render().splitlines() if ln.startswith("lbl{")
    ][0]
    assert line == 'lbl{name="we\\"ird\\\\path\\nline"} 1'
    with pytest.raises(ValueError, match="expected labels"):
        g.set(1)  # missing label
    with pytest.raises(ValueError, match="expected labels"):
        g.set(1, name="x", extra="y")
    with pytest.raises(ValueError, match="invalid metric name"):
        reg.counter("bad-name", "dash")


def test_histogram_cumulative_buckets_sum_count():
    reg = Registry()
    h = reg.histogram("lat_ms", "latency", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 5.0, 50.0, 5000.0):
        h.observe(v)
    text = reg.render()
    assert "# TYPE lat_ms histogram" in text
    assert 'lat_ms_bucket{le="1"} 1' in text
    assert 'lat_ms_bucket{le="10"} 3' in text
    assert 'lat_ms_bucket{le="100"} 4' in text
    assert 'lat_ms_bucket{le="+Inf"} 5' in text
    assert "lat_ms_count 5" in text
    assert "lat_ms_sum 5060.5" in text


def test_collector_runs_at_render_and_errors_are_contained():
    reg = Registry()
    calls = []

    def good():
        calls.append(1)
        reg.gauge("from_collector", "set at scrape").set(7)

    def bad():
        raise RuntimeError("broken component")

    reg.register_collector(good)
    reg.register_collector(bad)
    text = reg.render()
    assert calls == [1]
    assert "from_collector 7" in text
    text = reg.render()  # second scrape still renders
    assert "mlcomp_metrics_collector_errors_total 2" in text


def test_default_registry_is_shared():
    assert default_registry() is default_registry()


# ------------------------------------------------------------ tracer ring


def test_ring_buffer_evicts_oldest_and_counts_drops():
    tr = Tracer(max_events=3)
    for i in range(5):
        tr.instant(f"e{i}")
    evs = tr.events
    assert [e["name"] for e in evs] == ["e2", "e3", "e4"]
    assert tr.dropped == 2
    body = tr.export()
    other = body["otherData"]
    assert other["dropped_events"] == 2 and other["max_events"] == 3
    # every export carries the shared-clock stamps the fleet merger
    # (and any external consumer) aligns on
    assert other["clock_offset_us"] == (
        other["export_unix_us"] - other["export_trace_us"]
    )


def test_export_last_ms_windows_and_metadata():
    tr = Tracer(max_events=64)
    tr.instant("old", track="t1")
    time.sleep(0.08)
    tr.instant("new", track="t1")
    names = lambda body: [  # noqa: E731
        e["name"] for e in body["traceEvents"] if e["ph"] != "M"
    ]
    assert names(tr.export()) == ["old", "new"]
    assert names(tr.export(last_ms=40)) == ["new"]
    meta = [e for e in tr.export()["traceEvents"] if e["ph"] == "M"]
    assert meta and meta[0]["args"]["name"] == "t1"
    # a complete span straddling the cutoff stays (ts + dur intersects)
    tr2 = Tracer()
    with tr2.span("long"):
        time.sleep(0.06)
    assert names(tr2.export(last_ms=30)) == ["long"]


def test_export_last_ms_keeps_begins_of_clipped_async_spans():
    """An async span that STARTED before the window but is still open
    (or ended inside it) must keep its 'b' event — Perfetto cannot
    draw a span from an unmatched end."""
    tr = Tracer()
    tr.async_begin("request", 1, cat="req")   # ends inside the window
    tr.async_begin("request", 2, cat="req")   # still open
    tr.async_begin("request", 3, cat="req")   # ended before the window
    tr.async_end("request", 3, cat="req")
    time.sleep(0.08)
    tr.async_end("request", 1, cat="req")
    body = tr.export(last_ms=40)
    evs = [(e["ph"], e["id"]) for e in body["traceEvents"]
           if e["ph"] != "M"]
    assert ("b", "1") in evs and ("e", "1") in evs  # clipped: re-admitted
    assert ("b", "2") in evs                        # open: re-admitted
    assert ("b", "3") not in evs and ("e", "3") not in evs  # fully old


def test_span_yields_args_dict_for_results():
    tr = Tracer()
    with tr.span("lookup", prompt=9) as sp:
        sp["hit_tokens"] = 4
    (ev,) = tr.events
    assert ev["args"] == {"prompt": 9, "hit_tokens": 4}


def test_async_events_correlate_by_cat_and_id():
    tr = Tracer()
    tr.async_begin("dispatch", 7, cat="disp", inflight=2)
    tr.async_instant("first_token", 7, cat="disp")
    tr.async_end("dispatch", 7, cat="disp")
    phs = [(e["ph"], e["id"], e["cat"]) for e in tr.events]
    assert phs == [("b", "7", "disp"), ("n", "7", "disp"),
                   ("e", "7", "disp")]


def test_null_tracer_async_and_export_are_silent():
    t = null_tracer()
    t.async_begin("x", 1)
    t.async_end("x", 1)
    with t.span("y", track="z") as sp:
        sp["k"] = 1
    assert t.export()["traceEvents"] == []


def test_concurrent_save_and_append_stress(tmp_path):
    """The satellite race: save() serialized the LIVE event list
    outside the lock, so a concurrent span() append during json.dump
    raised RuntimeError.  N writer threads + a save loop must coexist
    and every written file must parse."""
    tr = Tracer(str(tmp_path / "t.json"), max_events=512)
    stop = threading.Event()
    errs = []

    def writer(i):
        try:
            while not stop.is_set():
                with tr.span(f"w{i}", n=1):
                    pass
                tr.instant(f"i{i}")
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errs.append(e)

    threads = [
        threading.Thread(target=writer, args=(i,)) for i in range(4)
    ]
    for t in threads:
        t.start()
    try:
        deadline = time.time() + 1.5
        while time.time() < deadline:
            path = tr.save()
            json.loads(open(path).read())  # every snapshot parses
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
    assert not errs, errs


# ----------------------------------------------- engine + serve surfaces


def _tiny_service(**kw):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mlcomp_tpu.models import create_model
    from mlcomp_tpu.serve import GenerationService
    from mlcomp_tpu.train.state import init_model

    model = create_model({
        "name": "transformer_lm", "vocab_size": 64, "hidden": 32,
        "layers": 1, "heads": 2, "mlp_dim": 64, "dtype": "float32",
    })
    prompt = jnp.asarray(np.random.RandomState(0).randint(1, 64, (1, 8)))
    params, _ = init_model(model, {"x": prompt}, jax.random.PRNGKey(0))
    kw.setdefault("batch_sizes", (1, 2))
    kw.setdefault("prompt_buckets", (16,))
    kw.setdefault("max_new_buckets", (8,))
    return GenerationService(model, {"params": params}, **kw)


def test_engine_latency_lifetime_samples_outlive_the_window():
    """/healthz 'samples' saturates at the reservoir's maxlen;
    'lifetime_samples' keeps counting (the long-run truth)."""
    from collections import deque

    svc = _tiny_service()
    try:
        eng = svc.engine
        eng._lat_ttft = deque(maxlen=2)  # shrink the window, host-only
        for i in range(3):
            svc.generate([1 + i, 2, 3], 2)
        lat = svc.stats()["latency"]
        assert lat["samples"] == 2           # the window saturated
        assert lat["lifetime_samples"] == 3  # the truth kept counting
    finally:
        svc.close()


def test_serve_metrics_and_trace_http_round_trip():
    from mlcomp_tpu.serve import make_http_server

    svc = _tiny_service(prefix_cache=True, prefill_chunk=8)
    httpd = make_http_server(svc, "127.0.0.1", 0, "toy")
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    port = httpd.server_address[1]
    try:
        for i in range(2):
            svc.generate([9, 10, 11, 12, 13, 14, 15, 16, i + 1], 3)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics"
        ) as r:
            assert r.headers["Content-Type"] == CONTENT_TYPE
            text1 = r.read().decode()
        assert "# TYPE mlcomp_engine_requests_total counter" in text1
        assert "mlcomp_engine_requests_total 2" in text1
        assert "# TYPE mlcomp_engine_ttft_ms histogram" in text1
        assert "mlcomp_prefix_cache_lookups_total 2" in text1
        svc.generate([9, 10, 11, 12, 13, 14, 15, 16, 50], 3)
        text2 = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics"
        ).read().decode()
        assert "mlcomp_engine_requests_total 3" in text2

        trace = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/trace?last_ms=600000"
        ).read())
        names = {e["name"] for e in trace["traceEvents"]}
        assert {"issue", "resolve", "dispatch", "request",
                "first_token", "prefill_chunk", "insert",
                "prefix_cache.lookup"} <= names
        # dispatch lifetime spans balance begin/end once the pipeline
        # has drained: the last request returns at its last token, with
        # a dispatch that carries nothing for it still in flight
        for _ in range(100):
            bs = [e for e in trace["traceEvents"]
                  if e["name"] == "dispatch" and e["ph"] == "b"]
            es = [e for e in trace["traceEvents"]
                  if e["name"] == "dispatch" and e["ph"] == "e"]
            if len(bs) == len(es):
                break
            time.sleep(0.05)
            trace = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/trace?last_ms=600000"
            ).read())
        assert bs and len(bs) == len(es)
        # malformed last_ms -> 400, not a stack dump
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/trace?last_ms=-5"
            )
        assert ei.value.code == 400
    finally:
        httpd.shutdown()
        httpd.server_close()
        svc.close()


def test_flight_recorder_and_history_can_be_disabled():
    svc = _tiny_service(
        flight_recorder_events=0, metrics_history_interval=0,
    )
    try:
        svc.generate([5, 6, 7], 2)
        assert svc.engine.recorder.events == []
        assert svc.trace()["traceEvents"] == []
        # history sampler off: the spine surfaces answer 404 (the
        # service raises, the HTTP layer maps)
        assert svc.history is None and svc.slo is None
        with pytest.raises(ValueError):
            svc.slo_status()
        with pytest.raises(ValueError):
            svc.metrics_history()
        # an SLO config without the sampler it needs is a misconfig
        with pytest.raises(ValueError):
            _tiny_service(
                metrics_history_interval=0, slo_config={},
            )
    finally:
        svc.close()


# ------------------------------------------------- report server /metrics


def test_report_server_metrics_exposition(tmp_db):
    import os
    import sys

    from mlcomp_tpu.dag.schema import DagSpec, TaskSpec
    from mlcomp_tpu.db.store import Store
    from mlcomp_tpu.report.server import start_in_thread

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools",
    ))
    import obs_check

    store = Store(tmp_db)
    dag = DagSpec(name="demo", project="p", tasks=(
        TaskSpec(name="a", executor="noop"),
        TaskSpec(name="b", executor="noop", depends=("a",)),
    ))
    store.submit_dag(dag)
    store.heartbeat("worker-0", chips=8, busy_chips=2)
    srv, port = start_in_thread(tmp_db)
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics"
        ) as r:
            assert r.headers["Content-Type"] == CONTENT_TYPE
            text = r.read().decode()
        samples, types = obs_check.parse_exposition(text)
        assert types["mlcomp_report_tasks"] == "gauge"
        assert samples["mlcomp_report_tasks"]['{status="not_ran"}'] == 2
        assert samples["mlcomp_report_workers_alive"][""] == 1
        assert samples["mlcomp_report_worker_chips"][
            '{worker="worker-0"}'
        ] == 8
        age = samples["mlcomp_report_worker_heartbeat_age_seconds"][
            '{worker="worker-0"}'
        ]
        assert 0 <= age < 60
        # no MLCOMP_TPU_SERVE_URL in the test env: serving series absent
        assert "mlcomp_serving_up" not in types
    finally:
        srv.shutdown()
        store.close()


def test_worker_heartbeat_registers_default_metrics(tmp_db):
    from mlcomp_tpu.db.store import Store
    from mlcomp_tpu.scheduler.worker import Worker

    store = Store(tmp_db)
    try:
        w = Worker(store, name="obs-w", chips=4,
                   load_jax_executors=False)
        w._host_info()
        m = default_registry()
        assert m.counter(
            "mlcomp_worker_heartbeats_total", labelnames=("worker",)
        ).value(worker="obs-w") >= 1
        assert m.gauge(
            "mlcomp_worker_chips", labelnames=("worker",)
        ).value(worker="obs-w") == 4
    finally:
        store.close()
