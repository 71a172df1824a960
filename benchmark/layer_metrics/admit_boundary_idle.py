"""decode engine: the share of the traced slice in which the chip sat idle
because an admission was completing (``admit_boundary_idle.steady`` /
``.offline``).  The device's idle gaps (``xplane.gaps`` of the first
chip's ops, plus the slice's two edges, so that they sum to what
``device_idle`` reports) are laid over the engine loop's spans: counted
is the idle time under an ``admission_complete`` span (the final drain
and the insert) and from that span's end to the end of the next
``issue`` (the pipeline refilling).

Logged before the result: ``trace.idle_by_span`` (innermost covering span
-> idle seconds of the slice, every gap counted once, ``outside_spans``
for what no span covers), ``trace.clock_residual_us``, and
``trace.issue_to_program_us`` (for the dispatches issued onto an idle
device: the device program's start after its ``issue`` span's opening
and after its close)."""

import bisect

import numpy as np

from benchmark import stats, xplane
from benchmark.harness import log
from benchmark.layer_metrics import loop_spans


class Idle:
    """Idle seconds of the device inside any interval of the slice, from
    the sorted, disjoint gaps (nanoseconds from the slice's opening)."""

    def __init__(self, gaps):
        g = np.asarray(gaps, np.float64).reshape(-1, 2)
        self.lo, self.hi = g[:, 0], g[:, 1]
        self.cum = np.concatenate([[0.0], np.cumsum(self.hi - self.lo)])

    def before(self, t):
        i = int(np.searchsorted(self.lo, t, side="right"))
        if i == 0:
            return 0.0
        return self.cum[i - 1] + min(max(t - self.lo[i - 1], 0.0),
                                     self.hi[i - 1] - self.lo[i - 1])

    def within(self, a, b):
        return (self.before(b) - self.before(a)) / 1e9 if b > a else 0.0

    @property
    def total_s(self):
        return float(self.cum[-1]) / 1e9


def device_gaps(tr):
    """The first chip's idle gaps inside the slice, edges included, in
    nanoseconds from the slice's opening."""
    w_lo, w_hi = tr.window
    first = tr.ops[sorted(tr.ops)[0]]
    if not first:
        return [(0.0, w_hi - w_lo)]
    out = [(a - w_lo, b - w_lo) for a, b in xplane.gaps(first)]
    head = min(s for _, s, _ in first)
    tail = max(e for _, _, e in first)
    if head > w_lo:
        out.insert(0, (0.0, head - w_lo))
    if tail < w_hi:
        out.append((tail - w_lo, w_hi - w_lo))
    return out


def issue_to_program(tr, issues, to_ns):
    """Pair the dispatch programs that start inside the slice with the
    ``issue`` spans that launched them (both in order; the pairing is the
    latest one in which every program starts after its issue opened), and
    for those issued onto an idle device say how long after the issue's
    opening and its close the program started."""
    w_lo = tr.window[0]
    progs = sorted((s - w_lo, e - w_lo) for _, s, e in
                   tr.module_spans(xplane.DISPATCH_PROGRAMS))
    inside = [j for j, p in enumerate(progs) if p[0] > 1.0]
    if not inside or not issues:
        return None
    opens = [to_ns(n.ts) for n in issues]
    # the recorder and the profiler's HOST plane agree to microseconds
    # (trace.clock_anchors); the profiler places its DEVICE plane beside
    # that to about a millisecond, another offset every capture, so a
    # program may read as starting before its issue opened
    tol = 2e6
    i0 = bisect.bisect_right(opens, progs[inside[0]][0] + tol) - 1
    while i0 >= 0 and not all(
        i0 + k < len(opens) and opens[i0 + k] <= progs[j][0] + tol
        for k, j in enumerate(inside)
    ):
        i0 -= 1
    if i0 < 0:
        return None
    after_open, after_close = [], []
    for k, j in enumerate(inside):
        if j == 0 or opens[i0 + k] < progs[j - 1][1]:
            continue  # the device was still running the one before
        after_open.append((progs[j][0] - opens[i0 + k]) / 1e3)
        after_close.append((progs[j][0] - to_ns(issues[i0 + k].end)) / 1e3)
    if not after_open:
        return {"paired": len(inside), "onto_idle_device": 0}
    return {
        "paired": len(inside), "onto_idle_device": len(after_open),
        "after_open_us": {"median": stats.median(after_open),
                          "min": min(after_open), "max": max(after_open)},
        "after_close_us": {"median": stats.median(after_close),
                           "min": min(after_close), "max": max(after_close)},
    }


def read(name, ctx):
    tr = ctx["trace"]
    if tr is None or not tr.devices or tr.window is None:
        return None
    bounds = loop_spans.slice_us(ctx, log, "admit_boundary_idle")
    to_ns = loop_spans.profiler_clock(ctx, log)
    if bounds is None or to_ns is None:
        return None
    roots = loop_spans.tree(ctx["events"])
    idle = Idle(device_gaps(tr))
    by_span, counted = {}, 0.0
    for root in roots:
        if root.end < bounds[0] or root.ts > bounds[1]:
            continue
        for lo, hi, path in loop_spans.segments(root):
            s = idle.within(to_ns(lo), to_ns(hi))
            if s > 0.0:
                by_span[path[-1]] = by_span.get(path[-1], 0.0) + s
                counted += s
    by_span["outside_spans"] = idle.total_s - counted
    log("trace.idle_by_span",
        dict(sorted(by_span.items(), key=lambda kv: -kv[1])))
    spans = [n for r in roots for n in r.walk()]
    issues = sorted((n for n in spans if n.name == "issue"),
                    key=lambda n: n.ts)
    pairing = issue_to_program(tr, issues, to_ns)
    if pairing is not None:
        log("trace.issue_to_program_us", pairing)
    starts = [n.ts for n in issues]
    share = 0.0
    for ac in (n for n in spans if n.name == "admission_complete"):
        i = bisect.bisect_left(starts, ac.end - loop_spans.EPS_US)
        refilled = issues[i].end if i < len(issues) else ac.end
        share += idle.within(to_ns(ac.ts), to_ns(refilled))
    return 100.0 * share / tr.window_s(ctx["slice_s"])
