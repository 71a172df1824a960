"""service: from a request's due time to the engine starting its
admission, 90th percentile.  The engine's flight recorder stamps a
request's ``request`` begin (at submit) and its ``admit`` instant on one
clock; the benchmark adds how late it submitted."""

from benchmark import stats


def read(name, ctx):
    begin, admit = {}, {}
    for ev in ctx["events"]:
        if ev.get("cat") != "req":
            continue
        if ev["name"] == "request" and ev["ph"] == "b":
            begin[ev["id"]] = ev["ts"]
        elif ev["name"] == "admit":
            admit[ev["id"]] = ev["ts"]
    t0 = ctx["window"]["t0"]
    waits = []
    for r in ctx["window"]["reqs"]:
        rid = str(getattr(r.future, "rid", ""))
        if rid in begin and rid in admit:
            late_ms = (r.sent - t0 - r.due) * 1e3
            waits.append((admit[rid] - begin[rid]) / 1e3 + late_ms)
    return stats.percentile(waits, 90) if waits else None
