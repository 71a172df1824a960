"""Span tracer: Chrome trace output + Trainer integration, plus the
trace-context helpers (W3C trace ids, traceparent parsing, export
clock stamps, per-request export filtering)."""

import json
import time

import pytest

from mlcomp_tpu.utils.trace import (
    Tracer,
    filter_export,
    get_tracer,
    make_trace_id,
    parse_traceparent,
    set_tracer,
    valid_trace_id,
)


def test_spans_and_counters_roundtrip(tmp_path):
    path = str(tmp_path / "t.json")
    tr = Tracer(path)
    with tr.span("outer", epoch=0):
        with tr.span("inner"):
            pass
        tr.instant("marker", note="hi")
    tr.counter("loss", {"train": 1.5})
    out = tr.save()
    body = json.loads(open(out).read())
    evs = body["traceEvents"]
    by_name = {e["name"]: e for e in evs}
    assert by_name["outer"]["ph"] == "X" and by_name["outer"]["dur"] >= 0
    assert by_name["outer"]["args"] == {"epoch": 0}
    assert by_name["inner"]["ts"] >= by_name["outer"]["ts"]
    assert by_name["marker"]["ph"] == "i"
    assert by_name["loss"]["ph"] == "C"
    assert by_name["loss"]["args"] == {"train": 1.5}


def test_null_tracer_is_silent():
    set_tracer(None)
    t = get_tracer()
    with t.span("x"):
        t.instant("y")
        t.counter("z", {"a": 1})
    with pytest.raises(ValueError):
        t.save()


def test_set_get_tracer():
    tr = Tracer()
    set_tracer(tr)
    assert get_tracer() is tr
    set_tracer(None)
    assert get_tracer() is not tr


def test_make_and_validate_trace_ids():
    tid = make_trace_id()
    assert valid_trace_id(tid) and len(tid) == 32
    assert make_trace_id() != tid  # 128 random bits
    assert not valid_trace_id("0" * 32)   # all-zero is reserved
    assert not valid_trace_id("XY" * 16)  # hex only
    assert not valid_trace_id(tid[:-1])   # length
    assert not valid_trace_id(tid + "\n")  # '$' would accept this
    assert not valid_trace_id(None)


def test_parse_traceparent():
    tid = "0af7651916cd43dd8448eb211c80319c"
    good = f"00-{tid}-00f067aa0ba902b7-01"
    assert parse_traceparent(good) == tid
    assert parse_traceparent(good.upper()) == tid  # case-insensitive
    # malformed headers yield None (mint instead), never raise
    for bad in (None, "", "garbage", f"ff-{tid}-00f067aa0ba902b7-01",
                f"00-{'0' * 32}-00f067aa0ba902b7-01",
                f"00-{tid}-{'0' * 16}-01", f"00-{tid}"):
        assert parse_traceparent(bad) is None


def test_export_carries_clock_stamps():
    tr = Tracer()
    with tr.span("x"):
        pass
    before = time.time() * 1e6
    body = tr.export()
    after = time.time() * 1e6
    od = body["otherData"]
    assert before <= od["export_unix_us"] <= after
    # the offset maps any event ts onto unix time
    ev = body["traceEvents"][0]
    unix = ev["ts"] + od["clock_offset_us"]
    assert abs(unix - od["export_unix_us"]) < 10e6


def test_filter_export_by_trace_id_and_rid():
    tid = make_trace_id()
    tr = Tracer()
    tr.async_begin("request", 7, cat="req", trace_id=tid)
    tr.async_instant("admit", 7, cat="req")
    with tr.span("insert", track="engine.loop", rid=7, trace_id=tid):
        pass
    # a neighbor request and request-agnostic engine spans
    tr.async_begin("request", 8, cat="req", trace_id=make_trace_id())
    with tr.span("issue", track="engine.loop", seq=1):
        pass
    tr.async_end("request", 7, cat="req")
    body = tr.export()
    by_tid = filter_export(body, trace_id=tid)
    non_meta = [e for e in by_tid["traceEvents"] if e["ph"] != "M"]
    assert [e["name"] for e in non_meta] == [
        "request", "admit", "insert", "request"
    ]
    assert by_tid["otherData"]["filter"]["rids"] == [7]
    # rid filter selects the same set; track metadata survives both
    by_rid = filter_export(body, rid=7)
    assert [e["name"] for e in by_rid["traceEvents"] if e["ph"] != "M"
            ] == [e["name"] for e in non_meta]
    assert any(e["ph"] == "M" for e in by_rid["traceEvents"])
    # an unknown id filters everything request-scoped out
    empty = filter_export(body, trace_id=make_trace_id())
    assert [e for e in empty["traceEvents"] if e["ph"] != "M"] == []


@pytest.mark.parametrize("how", ["whole", "last_ms", "one_request"])
def test_export_carries_one_clock_sync_on_the_perf_counter_clock(how):
    """Every export (whole ring, trailing window, one request's events)
    carries exactly one ``clock_sync`` record inside ``traceEvents``,
    and its epoch puts a span back on ``time.perf_counter()``: a
    consumer that stamped that clock around its own work (a device
    capture) can place the recorder's events beside it."""
    tr = Tracer(max_events=64)
    tr.async_begin("request", 7, cat="req", trace_id="ab" * 16)
    t_before = time.perf_counter()
    with tr.span("insert", track="engine.loop", rid=7):
        pass
    t_after = time.perf_counter()
    tr.complete("issue", tr.to_trace_us(t_before),
                (t_after - t_before) * 1e6, track="engine.loop", rid=7)
    body = {
        "whole": lambda: tr.export(),
        "last_ms": lambda: tr.export(last_ms=60000),
        "one_request": lambda: filter_export(tr.export(), rid=7),
    }[how]()
    sync = [e for e in body["traceEvents"] if e["name"] == "clock_sync"]
    assert len(sync) == 1 and sync[0]["ph"] == "M"
    epoch = sync[0]["args"]["epoch_perf_counter_s"]
    by_name = {e["name"]: e for e in body["traceEvents"] if e["ph"] == "X"}
    span = by_name["insert"]
    assert t_before <= epoch + span["ts"] / 1e6
    assert epoch + (span["ts"] + span["dur"]) / 1e6 <= t_after
    # a span recorded from the caller's own stamps comes back on them
    given = by_name["issue"]
    assert epoch + given["ts"] / 1e6 == pytest.approx(t_before, abs=1e-6)
    assert epoch + (given["ts"] + given["dur"]) / 1e6 == pytest.approx(
        t_after, abs=1e-6
    )
    # the same epoch as unix time: what otherData's offset already said
    assert sync[0]["args"]["epoch_unix_us"] == pytest.approx(
        body["otherData"]["clock_offset_us"], abs=1.0
    )


def test_trainer_writes_trace(tmp_path):
    from mlcomp_tpu.train.loop import Trainer

    path = str(tmp_path / "train_trace.json")
    cfg = {
        "model": {"name": "mlp", "hidden": [8], "num_classes": 4},
        "optimizer": {"name": "sgd", "lr": 0.1},
        "loss": "cross_entropy",
        "metrics": [],
        "epochs": 2,
        "seed": 0,
        "trace": {"path": path},
        "data": {
            "train": {
                "name": "synthetic_classification",
                "n": 16,
                "dim": 6,
                "num_classes": 4,
                "batch_size": 8,
            }
        },
    }
    trainer = Trainer(cfg)
    trainer.fit()
    set_tracer(None)
    evs = json.loads(open(path).read())["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"train_epoch", "data", "step", "loss"} <= names
    epochs = [e["args"]["epoch"] for e in evs if e["name"] == "train_epoch"]
    assert epochs == [0, 1]
