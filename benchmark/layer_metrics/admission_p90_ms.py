"""decode engine: how long one admission holds the engine's single
admission lane, 90th percentile over the window's requests: from the
request's ``admit`` instant (its prefill starts) to its ``inserted``
instant (its row is on the decode carry), both the flight recorder's
stamps on one clock.  Needs no device plane."""

from benchmark import stats
from benchmark.harness import log


def read(name, ctx):
    admit, inserted = {}, {}
    for ev in ctx["events"]:
        if ev.get("cat") != "req":
            continue
        if ev["name"] == "admit":
            admit[ev["id"]] = ev["ts"]
        elif ev["name"] == "inserted":
            inserted[ev["id"]] = ev["ts"]
    held = []
    for r in ctx["window"]["reqs"]:
        rid = str(getattr(r.future, "rid", ""))
        if rid in admit and rid in inserted:
            held.append((inserted[rid] - admit[rid]) / 1e3)
    if not held:
        log("trace.admission_p90_ms.skipped",
            "no request of the window has both admit and inserted")
        return None
    return stats.percentile(held, 90)
