"""service: the part of a request's queue wait that it spent behind
ANOTHER request's admission with a slot free (``lane_wait_p90_ms.<x>``),
90th percentile over the window's requests.  The engine's ``admit``
instant carries ``blocked_ms`` ``{lane, slot, pages}``: the boundaries
between the loop taking the request in and admitting it, by what the
queue's head waited for at each.  This reads ``lane``; it is a part of
``queue_wait_p90_ms`` (the rest: a slot, pages, the next boundary, the
benchmark's own lateness).  A program whose ``admit`` says no such thing
gives no number."""

from benchmark import stats
from benchmark.harness import log


def window_args(ctx, instant, key, who):
    """``key`` of the ``instant`` async instant's arguments, one value a
    request of the window whose instant carries it.  None where no
    instant in the ring carries it at all (a program that does not say);
    an empty list, and a ``trace.<who>.skipped`` line, where only the
    window's requests are missing.  Shared with ``admission_boundaries``."""
    said = {}
    for ev in ctx.get("events") or []:
        if ev.get("cat") == "req" and ev.get("name") == instant:
            value = (ev.get("args") or {}).get(key)
            if value is not None:
                said[ev["id"]] = value
    if not said:
        return None
    rids = (str(getattr(r.future, "rid", "")) for r in ctx["window"]["reqs"])
    mine = [said[rid] for rid in rids if rid in said]
    if not mine:
        log(f"trace.{who}.skipped",
            f"the ring holds no {instant} of a request of the window")
    return mine


def read(name, ctx):
    mine = window_args(ctx, "admit", "blocked_ms", "lane_wait_p90_ms")
    if not mine:
        return None
    log("trace.lane_wait.blocked_p90_ms", {
        k: stats.percentile([b[k] for b in mine], 90)
        for k in ("lane", "slot", "pages")})
    return stats.percentile([b["lane"] for b in mine], 90)
