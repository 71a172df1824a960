"""The readers of the engine loop's spans: on hand-made events and a
hand-made ``Trace``-shaped object with known gaps (known spans -> known
shares; a ring that lost the slice -> nothing), and on the events of a
rehearsal-width service (the profiler's session opens on the CPU, so the
whole ``--rehearsal 1 --trace 1`` path runs; it has no device plane, so
only the two clock-free readers report there)."""

import json

import pytest

from benchmark import cells

TID = 1
EPOCH = 1000.0          # the recorder's epoch on perf_counter, seconds
T_LO, T_HI = 1010.0, 1011.0   # the slice on perf_counter
W_LO = 5.0e12           # the slice's opening on the profiler's clock, ns
RESIDUAL_NS = -40e3     # the annotation opens 20 us in, closes 20 us early


def reader(name):
    return cells.layer_reader(name)


def span(name, ts_ms, dur_ms, **args):
    """A loop span, placed in milliseconds from the slice's opening."""
    ts = (T_LO - EPOCH) * 1e6 + ts_ms * 1e3
    return {"name": name, "ph": "X", "tid": TID, "pid": 1, "ts": ts,
            "dur": dur_ms * 1e3, "args": args}


def meta(clock=True, first_ts_us=0.0):
    out = [{"name": "thread_name", "ph": "M", "pid": 1, "tid": TID,
            "args": {"name": "engine.loop"}},
           {"name": "old", "ph": "i", "tid": 99, "pid": 1,
            "ts": first_ts_us, "args": {}}]
    if clock:
        out.append({"name": "clock_sync", "ph": "M", "pid": 1, "tid": 0,
                    "args": {"epoch_perf_counter_s": EPOCH,
                             "epoch_unix_us": 0.0}})
    return out


def loop_events():
    """Three boundaries inside the slice.  The second completes an
    admission: a drain (two dispatches), the insert, then the plain
    issue; the device idles from 100 ms (the drained dispatch ends) to
    112 ms (the next program starts, 1 ms before its issue closes)."""
    return [
        # boundary 1: 0..60 ms, a plain steady-state boundary
        span("boundary", 0, 60),
        span("maintenance", 0, 1),
        span("admission_tick", 1, 1),
        span("issue", 2, 2, seq=11, fused=False),
        span("resolve", 4, 54, seq=10),
        span("unpack", 58, 2, seq=10, tokens=8),
        # boundary 2: 60..120 ms, the admission's last boundary
        span("boundary", 60, 60),
        span("maintenance", 60, 1),
        span("admission_tick", 61, 47),
        span("admission_complete", 62, 45, rid=7, chunks=1, fused_chunks=1),
        span("join_drain", 62, 41),
        span("resolve", 62, 38.5, seq=11),
        span("unpack", 100.5, 2.5, seq=11, tokens=8),
        span("insert", 103, 4, rid=7),
        span("issue", 108, 5, seq=12, fused=False),
        span("resolve", 113, 6, seq=12),
        span("unpack", 119, 1, seq=12, tokens=8),
        # boundary 3: 120..400 ms, an idle engine
        span("boundary", 120, 280),
        span("maintenance", 120, 279),
        span("idle_wait", 121, 200),
        span("admission_tick", 399, 1),
    ]


class Trace:
    """What the readers use of ``xplane.Trace``: the first chip's ops,
    its program spans, the slice on the profiler's clock."""

    def __init__(self, busy_ms, programs_ms=()):
        def ns(ms):  # where the profiler saw a perf_counter instant
            return W_LO + ms * 1e6 + 0.5 * RESIDUAL_NS

        lo, hi = self.window = (
            W_LO, W_LO + (T_HI - T_LO) * 1e9 + RESIDUAL_NS
        )
        self.ops = {"/device:TPU:0": [  # clipped, as xplane.Trace does
            ("%fusion.1 = f32[8]", max(ns(a), lo), min(ns(b), hi))
            for a, b in busy_ms
        ]}
        self._programs = [("jit_dispatch(1)", ns(a), ns(b))
                          for a, b in programs_ms]
        # the benchmark's own annotations: one submit, 300 ms in
        self.host = [("bench.slice", lo, hi),
                     ("bench.submit", ns(300.0), ns(300.5))]

    @property
    def devices(self):
        return sorted(self.ops)

    def window_s(self, fallback_s):
        return (self.window[1] - self.window[0]) / 1e9

    def module_spans(self, pattern):
        return list(self._programs)


class Sent:
    sent = T_LO + 0.300  # stamped as its annotation opened


def ctx(events, trace=None, **more):
    return {"events": events, "slice": (T_LO, T_HI), "slice_s": 1.0,
            "trace": trace, "window": {"reqs": [Sent], "t0": T_LO - 20.0},
            **more}  # more wins


def test_host_ms_per_dispatch_is_boundary_time_less_blocked_over_issues(
        capsys):
    got = reader("host_ms_per_dispatch.offline")(
        "host_ms_per_dispatch.offline", ctx(meta() + loop_events()))
    # 400 ms of boundaries less resolve (54 + 38.5 + 6) and idle_wait
    # (200), over two issue spans
    assert got == pytest.approx((400 - 98.5 - 200) / 2)
    lines = dict(ln.split(" ", 1) for ln in
                 capsys.readouterr().out.splitlines())
    self_ms = json.loads(lines["trace.host_self_ms"])
    assert self_ms["boundary"] == pytest.approx(0.0, abs=1e-9)
    assert self_ms["unpack"] == pytest.approx((2 + 2.5 + 1) / 2)
    assert self_ms["maintenance"] == pytest.approx((1 + 1 + 79) / 2)
    assert sum(self_ms.values()) == pytest.approx(400 / 2)


def test_host_ms_counter_is_the_engines_own_books_between_two_stats():
    mod = cells._load_py(cells.HERE / "layer_metrics"
                         / "host_ms_per_dispatch.py")
    st = lambda n, per: {"engine": {  # noqa: E731
        "dispatches": n, "pipeline": {"host_ms_per_dispatch": per}}}
    assert mod.counter_ms({"stats0": st(100, 2.0), "stats1": st(300, 4.0)}
                          ) == pytest.approx((1200 - 200) / 200)
    # the spans from a window's opening on, a boundary cut where it
    # straddles it: from 58 ms (the unpack of seq 10 opens) the loop ran
    # 342 ms, less resolve (38.5 + 6) and idle_wait (200), one issue (seq 12)
    loop = mod.loop_spans.tree(meta() + loop_events())
    at = (T_LO - EPOCH) * 1e6
    assert mod.host_ms_from(loop, at + 58e3) == pytest.approx(342 - 244.5)
    assert mod.host_ms_from(loop, at + 110e3) is None
    # the parent's engine keeps no such counter: nothing, and no raise
    parent = {"engine": {"dispatches": 5, "pipeline": {}}}
    assert mod.counter_ms({"stats0": parent, "stats1": parent}) is None


@pytest.mark.parametrize("events,why", [
    (meta(clock=False) + loop_events(), "clock_sync"),
    (meta(first_ts_us=(T_LO - EPOCH) * 1e6 + 5.0)
     + [e for e in loop_events() if e["ts"] > (T_LO - EPOCH) * 1e6 + 5.0],
     "no longer holds the slice"),
    (meta(), "no issue span"),
])
def test_a_reader_without_its_anchors_reads_nothing_and_says_why(
        events, why, capsys):
    tr = Trace([(0, 100), (112, 1000)])
    for name in ("host_ms_per_dispatch.steady", "admit_boundary_idle.steady"):
        if why == "no issue span" and name.startswith("admit"):
            continue  # no admission in the slice is a true 0, see below
        assert reader(name)(name, ctx(events, tr)) is None
        assert why in capsys.readouterr().out


def test_admit_boundary_idle_counts_the_gap_under_the_admission(capsys):
    # busy 0.5..100 ms, idle 100..112 (12 ms = 1.2% of the slice), busy to
    # 500, idle 500..502 after the loop's last span, busy to 999
    tr = Trace([(0.5, 60), (40, 100), (112, 500), (502, 999)],
               programs_ms=[(0, 100), (112, 160)])
    got = reader("admit_boundary_idle.offline")(
        "admit_boundary_idle.offline", ctx(meta() + loop_events(), tr))
    lines = dict(ln.split(" ", 1) for ln in
                 capsys.readouterr().out.splitlines())
    idle = json.loads(lines["trace.idle_by_span"])
    window_ms = (tr.window[1] - tr.window[0]) / 1e6
    # every gap once: 12 ms in the admission, 2 ms far outside any span,
    # and the slice's two edges (the slice opens 20 us after its
    # perf_counter stamp and closes 20 us before the other)
    assert sum(idle.values()) * 1e3 == pytest.approx(
        12 + 2 + 0.48 + 0.98, abs=1e-3)
    assert idle["maintenance"] * 1e3 == pytest.approx(0.48, abs=1e-3)
    assert idle["unpack"] * 1e3 == pytest.approx(2.5, abs=1e-3)   # 100.5..103
    assert idle["resolve"] * 1e3 == pytest.approx(0.5, abs=1e-3)  # 100..100.5
    assert idle["insert"] * 1e3 == pytest.approx(4.0, abs=1e-3)
    assert idle["admission_tick"] * 1e3 == pytest.approx(1.0, abs=1e-3)
    assert idle["issue"] * 1e3 == pytest.approx(4.0, abs=1e-3)    # 108..112
    assert idle["outside_spans"] * 1e3 == pytest.approx(2.98, abs=1e-3)
    # under admission_complete (100..107) + to the next issue's end (..112)
    assert got == pytest.approx(100.0 * 12 / window_ms, rel=1e-6)
    assert json.loads(lines["trace.clock_residual_us"]) == pytest.approx(-40)
    # the submit's own pair of stamps, 0.3 s in: the profiler saw it 20 us
    # earlier than the opening anchor alone says, so the slice's annotation
    # took 20 us to open, and that is the shift the mapping takes
    anchors = json.loads(lines["trace.clock_anchors"])
    assert anchors["shift_us"] == pytest.approx(-20, abs=0.01)
    (at_s, off_us), = anchors["submits"]
    assert at_s == pytest.approx(0.3) and off_us == pytest.approx(-20, abs=0.01)
    pair = json.loads(lines["trace.issue_to_program_us"])
    assert pair["paired"] == 1 and pair["onto_idle_device"] == 1
    assert pair["after_open_us"]["median"] == pytest.approx(4000, abs=1)
    assert pair["after_close_us"]["median"] == pytest.approx(-1000, abs=1)


def test_a_device_plane_that_leads_the_host_plane_still_pairs(capsys):
    # the profiler places the device plane to about a millisecond: here the
    # program reads as starting 0.2 ms BEFORE its issue span opens (108 ms)
    tr = Trace([(0.5, 100), (107.8, 999)],
               programs_ms=[(0, 100), (107.8, 160)])
    reader("admit_boundary_idle.offline")(
        "admit_boundary_idle.offline", ctx(meta() + loop_events(), tr))
    lines = dict(ln.split(" ", 1) for ln in
                 capsys.readouterr().out.splitlines())
    pair = json.loads(lines["trace.issue_to_program_us"])
    assert pair["paired"] == 1 and pair["onto_idle_device"] == 1
    assert pair["after_open_us"]["median"] == pytest.approx(-200, abs=1)


def test_no_admission_in_the_slice_is_a_true_zero(capsys):
    tr = Trace([(0, 100), (112, 1000)])
    events = meta() + [e for e in loop_events()
                       if e["name"] != "admission_complete"]
    # no submit pairs up inside the slice either: half the residual a side
    assert reader("admit_boundary_idle.steady")(
        "admit_boundary_idle.steady",
        ctx(events, tr, window={"reqs": []})) == 0.0
    lines = dict(ln.split(" ", 1) for ln in
                 capsys.readouterr().out.splitlines())
    assert json.loads(lines["trace.clock_anchors"]) == {
        "shift_us": pytest.approx(-20), "submits": []}
    # and without a device plane (the CPU rehearsal) there is no reading
    assert reader("admit_boundary_idle.steady")(
        "admit_boundary_idle.steady", ctx(events, None)) is None


def test_admission_p90_is_admit_to_inserted_per_request_of_the_window():
    class Fut:
        def __init__(self, rid):
            self.rid = rid

    class Req:
        def __init__(self, rid):
            self.future = Fut(rid)

    events = []
    for rid, (admit_ms, ins_ms) in {1: (0, 100), 2: (50, 400), 3: (60, 90),
                                    9: (0, 9999)}.items():  # 9: a warm-up
        for name, at in (("admit", admit_ms), ("inserted", ins_ms)):
            events.append({"name": name, "ph": "n", "cat": "req",
                           "id": str(rid), "ts": at * 1e3, "args": {}})
    events.append({"name": "admit", "ph": "n", "cat": "req", "id": "4",
                   "ts": 0.0, "args": {}})  # never inserted: left out
    win = {"reqs": [Req(1), Req(2), Req(3), Req(4)]}
    read = reader("admission_p90_ms")
    assert read("admission_p90_ms", {"events": events, "window": win}) == 350
    # the parent's engine stamps no ``inserted``: nothing, and no raise
    old = [e for e in events if e["name"] != "inserted"]
    assert read("admission_p90_ms", {"events": old, "window": win}) is None


def test_the_rehearsal_reads_the_clock_free_metrics_from_a_live_service(
        rehearse):
    seen, res = rehearse("--workload", "chat-steady", "--seconds", "5",
                         "--trace", "1", "--seed", str(2**31 + 77))
    assert res["correct"] is True and res["metrics"] == {}
    got = res["rehearsal_metrics"]
    assert got["host_ms_per_dispatch.steady"]["value"] > 0
    assert got["admission_p90_ms"]["value"] > 0
    assert "admit_boundary_idle.steady" not in got  # no device plane
    self_ms = seen["trace.host_self_ms"]
    assert {"boundary", "maintenance", "admission_tick", "issue", "resolve",
            "unpack", "admission_complete", "insert"} <= set(self_ms)
    assert abs(self_ms["boundary"]) < 1e-6  # the children tile it
    host = sum(v for k, v in self_ms.items()
               if k not in ("resolve", "idle_wait"))
    assert got["host_ms_per_dispatch.steady"]["value"] == pytest.approx(host)
    # the engine's own books between the two stats() calls, and the spans
    # of that same stretch: one set of stamps feeds both
    books = seen["trace.host_ms_counter"]
    # one-sided: the counter's stretch opens at the first stats() call,
    # and what the loop booked from there to the window's opening (a
    # stall while the benchmark settles its gc) weighs on 70 dispatches
    assert 0 < books["spans_same_stretch"] <= 1.1 * books["counter"]


def test_the_closed_loop_rehearsal_reads_its_host_metric(rehearse):
    seen, res = rehearse("--workload", "batch-offline", "--seconds", "5",
                         "--trace", "1", "--seed", "78")
    assert res["correct"] is True
    assert res["rehearsal_metrics"]["host_ms_per_dispatch.offline"][
        "value"] > 0
    assert "admission_p90_ms" not in res["rehearsal_metrics"]
