"""decode engine: what the loop thread itself costs per dispatch
(``host_ms_per_dispatch.steady`` / ``.offline``).  Over the ``boundary``
spans that open inside the traced slice: their duration less every
``resolve`` (blocked on the device) and ``idle_wait`` (blocked on an
empty queue) under them, over the ``issue`` spans under them.  Needs no
device plane.  Also logs ``trace.host_self_ms`` (span name -> self
milliseconds per dispatch, the same boundaries; it sums to the loop's
whole time per dispatch) and ``trace.host_ms_counter``: the engine's own
``host_ms`` counter per dispatch between the two ``stats()`` calls around
the window, beside the same figure from the spans from the window's
opening on, where the ring still holds it.  The counter's stretch opens
a little earlier, at the first ``stats()`` call, so it also holds what
the loop thread booked while the benchmark settled its garbage collector
(a stall on the interpreter's lock; nothing over a window's hundreds of
dispatches, visible over a rehearsal's seventy).  The slice and the
window differ where the profiler's stop slows the host."""

from benchmark.harness import log
from benchmark.layer_metrics import loop_spans

BLOCKED = ("resolve", "idle_wait")


def counter_ms(ctx):
    """``stats1 - stats0`` of the engine's own books: host ms per
    dispatch resolved in between, or None where the program keeps none."""
    try:
        tot = []
        for st in (ctx["stats0"]["engine"], ctx["stats1"]["engine"]):
            n = st["dispatches"]
            tot.append((n, (st["pipeline"]["host_ms_per_dispatch"] or 0.0) * n))
    except (KeyError, TypeError):
        return None
    (n0, h0), (n1, h1) = tot
    return (h1 - h0) / (n1 - n0) if n1 > n0 else None


def host_ms_from(loop, t_us):
    """The spans' host ms per dispatch from ``t_us`` on (a boundary that
    straddles it is cut there); None without an issue after it."""
    late = [b for b in loop if b.end > t_us]
    host_us = sum(hi - max(lo, t_us)
                  for b in late for lo, hi, path in loop_spans.segments(b)
                  if hi > t_us and path[-1] not in BLOCKED)
    issues = sum(1 for b in late for n in b.walk()
                 if n.name == "issue" and n.ts >= t_us)
    return host_us / 1e3 / issues if issues else None


def self_ms_per_dispatch(boundaries):
    """Span name -> self milliseconds per ``issue`` span, over the given
    ``boundary`` spans; None without an issue."""
    nodes = [n for b in boundaries for n in b.walk()]
    issues = sum(1 for n in nodes if n.name == "issue")
    if not issues:
        return None
    self_ms = {}
    for n in nodes:
        self_ms[n.name] = self_ms.get(n.name, 0.0) + n.self_us / 1e3 / issues
    return self_ms


def host_ms(self_ms):
    # resolve and idle_wait have no children: their self time is all of them
    return sum(v for k, v in self_ms.items() if k not in BLOCKED)


def read(name, ctx):
    bounds = loop_spans.slice_us(ctx, log, "host_ms_per_dispatch")
    if bounds is None:
        return None
    events = ctx["events"]
    loop = [b for b in loop_spans.tree(events) if b.name == "boundary"]
    lo_us, hi_us = bounds
    self_ms = self_ms_per_dispatch(
        [b for b in loop if lo_us <= b.ts < hi_us])
    if self_ms is None:
        log("trace.host_ms_per_dispatch.skipped",
            "no issue span under a boundary of the slice")
        return None
    log("trace.host_self_ms",
        dict(sorted(self_ms.items(), key=lambda kv: -kv[1])))
    counter = counter_ms(ctx)
    if counter is not None:
        books = {"counter": counter}
        t0_us = (ctx["window"]["t0"] - loop_spans.epoch_s(events)) * 1e6
        if loop_spans.earliest_us(events) <= t0_us:
            books["spans_same_stretch"] = host_ms_from(loop, t0_us)
        log("trace.host_ms_counter", books)
    return host_ms(self_ms)
