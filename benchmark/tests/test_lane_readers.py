"""The readers of the admission lane's books, on hand-made events and
``stats()`` pairs (known spans and counts -> known shares; a ring that
shed the slice -> skipped by name; a program that keeps no such books ->
nothing, and no raise), and on a rehearsal-width service, where all five
report."""

import pytest

from benchmark import cells
from benchmark.tests.test_loop_spans import EPOCH, T_HI, T_LO, meta, span

LANE_TID = 7
OFFLINE = ("offline", "rollout", "mixedlen", "continuation")


def reader(name):
    return cells.layer_reader(name)


def lane_meta(**kw):
    return meta(**kw) + [{"name": "thread_name", "ph": "M", "pid": 1,
                          "tid": LANE_TID, "args": {"name": "engine.lane"}}]


def admission(ts_ms, dur_ms, rid=1, **args):
    """An ``admission`` span on the lane's track, in ms from the slice's
    opening."""
    ev = span("admission", ts_ms, dur_ms, rid=rid, **args)
    ev["tid"] = LANE_TID
    return ev


class Fut:
    def __init__(self, rid):
        self.rid = rid


class Req:
    def __init__(self, rid):
        self.future = Fut(rid)


def stats(attended=0, starved=0, total=0, ledger=True):
    att = {"rows_attended": attended, "rows_total": total}
    if ledger:
        att["rows_starved"] = starved
    return {"engine": {"attention": att}}


def test_the_row_shares_are_run_deltas_of_the_three_way_ledger():
    ctx = {"stats0": stats(100, 10, 200), "stats1": stats(700, 250, 1200)}
    live = reader("live_rows_share.mixedlen")
    starved = reader("starved_rows_share.mixedlen")
    assert live("live_rows_share.mixedlen", ctx) == pytest.approx(60.0)
    assert starved("starved_rows_share.mixedlen", ctx) == pytest.approx(24.0)
    # a parent that counts attended and total but keeps no ledger, a
    # window without a dispatch, no stats at all: nothing, and no raise
    for s0, s1 in ((stats(1, 0, 2, ledger=False), stats(5, 0, 9, ledger=False)),
                   (stats(5, 1, 9), stats(5, 1, 9)), ({}, {}), (None, None)):
        ctx = {"stats0": s0, "stats1": s1}
        assert live("live_rows_share.offline", ctx) is None
        assert starved("starved_rows_share.offline", ctx) is None


def lane_ctx(events, **more):
    return {"events": events, "slice": (T_LO, T_HI),
            "window": {"reqs": []}, **more}


def test_lane_busy_share_is_the_union_of_the_lanes_spans_in_the_slice():
    events = lane_meta() + [
        admission(-50, 150, rid=1),        # straddles the opening: 100 in
        admission(300, 200, rid=2),
        admission(950, 100, rid=3),        # straddles the close: 50 in
        span("boundary", 0, 60),           # the loop's track is not read
    ]
    read = reader("lane_busy_share.steady")
    assert read("lane_busy_share.steady", lane_ctx(events)) == pytest.approx(
        35.0)
    # a lane track with no admission in the slice is a true zero
    quiet = lane_meta() + [admission(-500, 100)]
    assert read("lane_busy_share.prefill", lane_ctx(quiet)) == 0.0


def test_a_shed_ring_is_skipped_by_name(capsys):
    # the ring's oldest event is younger than the slice's opening
    events = lane_meta(first_ts_us=(T_HI - EPOCH) * 1e6) + [
        admission(2000, 100)]
    read = reader("lane_busy_share.mixedlen")
    assert read("lane_busy_share.mixedlen", lane_ctx(events)) is None
    assert "trace.lane_busy_share.skipped" in capsys.readouterr().out


def test_a_program_without_a_lane_track_or_books_reads_nothing():
    events = meta() + [span("boundary", 0, 60)]
    for name in ("lane_busy_share.steady", "lane_wait_p90_ms.steady",
                 "admission_boundaries.steady"):
        ctx = lane_ctx(events, stats0=stats(ledger=False),
                       stats1=stats(ledger=False))
        ctx["window"]["reqs"] = [Req(1)]
        assert reader(name)(name, ctx) is None


def req_event(name, rid, **args):
    return {"name": name, "ph": "n", "cat": "req", "id": str(rid),
            "ts": 0.0, "args": args}


def test_lane_wait_and_boundaries_read_the_instants_arguments():
    # ten requests: the nearest-rank p90 is the ninth smallest
    waits = {rid: ms for rid, ms in enumerate(
        (0.0, 10.0, 40.0, 0.0, 80.0, 5.0, 0.0, 64.0, 0.0, 2.0), start=1)}
    events = []
    for rid, lane in waits.items():
        events.append(req_event(
            "admit", rid, queued_ms=lane + 3.0,
            blocked_ms={"lane": lane, "slot": 2.0, "pages": 0.0}))
        events.append(req_event("inserted", rid, chunks=rid, fused_chunks=0,
                                boundaries=rid, of=8))
    # a warm-up's instants (not a request of the window) stay out
    events.append(req_event("admit", 99, queued_ms=999.0,
                            blocked_ms={"lane": 999.0, "slot": 0, "pages": 0}))
    events.append(req_event("inserted", 99, boundaries=99))
    win = {"reqs": [Req(r) for r in waits]}
    ctx = {"events": events, "window": win}
    got = reader("lane_wait_p90_ms.steady")("lane_wait_p90_ms.steady", ctx)
    assert got == 64.0
    got = reader("admission_boundaries.prefill")(
        "admission_boundaries.prefill", ctx)
    assert got == pytest.approx(5.5)       # mean of 1..10
    # the parent's instants carry no such arguments
    old = [req_event(e["name"], e["id"]) for e in events]
    ctx = {"events": old, "window": win}
    assert reader("lane_wait_p90_ms.steady")(
        "lane_wait_p90_ms.steady", ctx) is None
    assert reader("admission_boundaries.steady")(
        "admission_boundaries.steady", ctx) is None


def test_the_lanes_entries_name_their_cells_and_files():
    """The count and the place in the list are left open: a later cell
    brings its own ``lane_busy_share.<x>``, and a later PR appends."""
    spec = cells.benchmark_spec()
    mine = [m for m in spec["per_layer"] if m["name"].split(".")[0] in (
        "live_rows_share", "starved_rows_share", "lane_busy_share",
        "lane_wait_p90_ms", "admission_boundaries")]
    assert {m["name"] for m in mine} >= {
        f"{family}.{cell}" for family, suffixes in (
            ("live_rows_share", OFFLINE), ("starved_rows_share", OFFLINE),
            ("lane_busy_share", OFFLINE + ("steady", "prefill")),
            ("lane_wait_p90_ms", ("steady", "prefill")),
            ("admission_boundaries",
             ("steady", "prefill", "mixedlen", "continuation")),
        ) for cell in suffixes}
    e2e = {m["name"]: m.get("workloads") for m in spec["end_to_end"]}
    for m in mine:
        assert cells.layer_reader(m["name"]) is not None
        (cell,) = m["workloads"]
        assert cell in e2e[m["moves"]]
        assert m in cells.Cell(cell).per_layer()


def test_a_rehearsal_width_service_reports_all_five(rehearse):
    seen, res = rehearse("--workload", "chat-steady", "--seconds", "5",
                         "--trace", "1", "--seed", str(2**31 + 39))
    assert res["correct"] is True and res["metrics"] == {}
    got = res["rehearsal_metrics"]
    assert 0.0 < got["lane_busy_share.steady"]["value"] <= 100.0
    assert got["lane_wait_p90_ms.steady"]["value"] >= 0.0
    assert got["admission_boundaries.steady"]["value"] >= 1.0
    # every reader the accepted benchmark had still reads
    assert got["host_ms_per_dispatch.steady"]["value"] > 0
    assert got["admission_p90_ms"]["value"] > 0
    assert got["queue_wait_p90_ms"]["value"] >= 0

    seen, res = rehearse("--workload", "batch-offline", "--seconds", "5",
                         "--trace", "1", "--seed", "39")
    assert res["correct"] is True
    got = res["rehearsal_metrics"]
    live = got["live_rows_share.offline"]["value"]
    starved = got["starved_rows_share.offline"]["value"]
    assert live > 0 and starved >= 0 and live + starved <= 100.0 + 1e-9
    assert 0.0 <= got["lane_busy_share.offline"]["value"] <= 100.0
    assert got["host_ms_per_dispatch.offline"]["value"] > 0
