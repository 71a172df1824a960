"""decode engine: the share of the traced slice in which the engine's
one admission lane was held (``lane_busy_share.<x>``).  The flight
recorder's ``engine.lane`` track carries one ``admission`` span per
admission, ``admit`` stamp to ``inserted`` stamp; one lane, so the spans
do not overlap and their union inside the slice is the lane's busy time.
Needs no device plane.

One quantity, one source: where the ring no longer holds the slice the
reader says so by name (``trace.lane_busy_share.skipped``) and reads
nothing, as the loop's other span readers do.  The engine's own
``lane_busy_ms`` (``stats()["engine"]["admission"]``) is fed by the same
stamps, but a ``stats1 - stats0`` of it covers the whole window, ramp
and drain included, which is another number."""

from benchmark.harness import log
from benchmark.layer_metrics import loop_spans

LANE_TRACK = "engine.lane"


def lane_spans(events):
    """[(lo_us, hi_us)] of the ``admission`` spans, in time order; None
    where the recorder has no such track (a program without one, or
    no admission yet)."""
    tids = {ev["tid"] for ev in events
            if ev.get("ph") == "M" and ev.get("name") == "thread_name"
            and ev["args"].get("name") == LANE_TRACK}
    if not tids:
        return None
    return sorted((float(ev["ts"]), float(ev["ts"]) + float(ev["dur"]))
                  for ev in events
                  if ev.get("ph") == "X" and ev.get("tid") in tids
                  and ev.get("name") == "admission")


def busy_us(spans, lo, hi):
    """Length of the union of ``spans`` inside [lo, hi]."""
    total, at = 0.0, lo
    for a, b in spans:
        a, b = max(a, at), min(b, hi)
        if b > a:
            total += b - a
            at = b
    return total


def read(name, ctx):
    spans = lane_spans(ctx.get("events") or [])
    if spans is None:
        return None     # the program has no lane track
    bounds = loop_spans.slice_us(ctx, log, "lane_busy_share")
    if bounds is None:
        return None
    lo, hi = bounds
    return 100.0 * busy_us(spans, lo, hi) / (hi - lo)
