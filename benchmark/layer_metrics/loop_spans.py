"""decode engine: what the readers of the engine loop's spans share.

The engine's flight recorder (``service.trace()``) puts one ``boundary``
span per iteration of the drive loop on the one-thread ``engine.loop``
track, tiled by ``maintenance`` (child ``idle_wait``), ``admission_tick``
(children ``admission_start``, ``prefill_chunk``, ``join_drain``,
``admission_complete`` -> ``insert``, ...), ``issue``, ``resolve`` and
``unpack``.  One thread, so
containment IS the parent relation, and a span's self time is its
duration minus its children's.

Clocks: an event's ``ts`` counts microseconds from the recorder's epoch,
which the export's ``clock_sync`` record gives as a ``perf_counter``
reading.  The traced slice has both other clocks for one interval:
``ctx["slice"]`` is ``perf_counter`` just outside the ``bench.slice``
annotation, ``ctx["trace"].window`` the same annotation in the
profiler's nanoseconds, the timeline the device ops are on.  The two
lengths differ by what opening and closing the annotation cost (the
residual); ``profiler_clock`` says how it is shared out.

Not a reader itself: no metric is named ``loop_spans``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

LOOP_TRACK = "engine.loop"
EPS_US = 1e-3  # two stamps a nanosecond apart are one stamp


class Node:
    __slots__ = ("name", "ts", "end", "args", "children")

    def __init__(self, ev: Dict[str, Any]):
        self.name = ev["name"]
        self.ts = float(ev["ts"])
        self.end = self.ts + float(ev["dur"])
        self.args = ev.get("args") or {}
        self.children: List["Node"] = []

    @property
    def dur(self) -> float:
        return self.end - self.ts

    @property
    def self_us(self) -> float:
        return self.dur - sum(c.dur for c in self.children)

    def walk(self) -> Iterator["Node"]:
        yield self
        for c in self.children:
            yield from c.walk()


def epoch_s(events: List[Dict[str, Any]]) -> Optional[float]:
    """The recorder's epoch as a ``perf_counter`` reading, or None where
    the export has no ``clock_sync`` record."""
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "clock_sync":
            return float(ev["args"]["epoch_perf_counter_s"])
    return None


def tree(events: List[Dict[str, Any]]) -> List[Node]:
    """The ``engine.loop`` track's complete spans as a forest, nested by
    containment, roots and children in time order."""
    tids = {ev["tid"] for ev in events
            if ev.get("ph") == "M" and ev.get("name") == "thread_name"
            and ev["args"].get("name") == LOOP_TRACK}
    nodes = sorted(
        (Node(ev) for ev in events
         if ev.get("ph") == "X" and ev.get("tid") in tids),
        key=lambda n: (n.ts, -n.end),
    )
    roots: List[Node] = []
    stack: List[Node] = []
    for n in nodes:
        while stack and stack[-1].end <= n.ts + EPS_US:
            stack.pop()
        (stack[-1].children if stack else roots).append(n)
        stack.append(n)
    return roots


def segments(node: Node, path: Tuple[str, ...] = (),
             ) -> Iterator[Tuple[float, float, Tuple[str, ...]]]:
    """``node``'s stretch of the timeline cut where its children open and
    close: (lo_us, hi_us, the names from the root down to the innermost
    span that covers that piece)."""
    path = path + (node.name,)
    at = node.ts
    for c in node.children:
        if c.ts > at:
            yield at, c.ts, path
        yield from segments(c, path)
        at = c.end
    if node.end > at:
        yield at, node.end, path


def earliest_us(events: List[Dict[str, Any]]) -> Optional[float]:
    """The oldest stamp the ring still holds, or None for an empty one."""
    return min((ev["ts"] for ev in events if "ts" in ev), default=None)


def slice_us(ctx: Dict[str, Any], log: Callable[[str, Any], None],
             who: str) -> Optional[Tuple[float, float]]:
    """The traced slice on the recorder's clock, or None (and a
    ``trace.<who>.skipped`` line saying why) when an anchor is missing or
    the ring no longer holds the slice's start."""
    events = ctx.get("events") or []
    epoch = epoch_s(events)
    t_lo, t_hi = ctx.get("slice") or (None, None)
    why = None
    if epoch is None:
        why = "the recorder's export carries no clock_sync record"
    elif t_lo is None or t_hi is None:
        why = "the run has no traced slice"
    else:
        lo, hi = (t_lo - epoch) * 1e6, (t_hi - epoch) * 1e6
        first = earliest_us(events)
        if first is None or first > lo:
            why = (f"the ring no longer holds the slice: its earliest event "
                   f"is at {first} us, the slice opens at {lo} us")
    if why is not None:
        log(f"trace.{who}.skipped", why)
        return None
    return lo, hi


def profiler_clock(ctx: Dict[str, Any], log: Callable[[str, Any], None],
                   ) -> Optional[Callable[[float], float]]:
    """Recorder microseconds -> the profiler's nanoseconds counted from the
    slice's opening (``ctx["trace"].window[0]``).  None without a device
    capture that holds the ``bench.slice`` span.

    The slice's two anchors bracket the truth: the annotation opened some
    ``a`` >= 0 after ``t_lo`` was stamped and closed some ``b`` >= 0 before
    ``t_hi`` was, and the residual between the anchors is ``-(a + b)``
    (logged as ``trace.clock_residual_us``).  Each ``bench.submit``
    annotation of the slice is a third pair of the same clocks, stamped the
    other way round (``perf_counter`` a few microseconds AFTER the
    annotation opened), so it bounds ``-a`` from below: the mapping shifts
    by the largest such bound, or by half the residual where the slice saw
    no submit (``trace.clock_anchors``: the shift taken and each pair)."""
    tr = ctx.get("trace")
    epoch = epoch_s(ctx.get("events") or [])
    t_lo, t_hi = ctx.get("slice") or (None, None)
    if (tr is None or tr.window is None or epoch is None
            or t_lo is None or t_hi is None):
        return None
    w_lo, w_hi = tr.window
    residual_ns = (w_hi - w_lo) - (t_hi - t_lo) * 1e9
    log("trace.clock_residual_us", residual_ns / 1e3)
    spans = sorted(s for n, s, _ in tr.host
                   if n == "bench.submit" and w_lo <= s <= w_hi)
    sent = sorted(r.sent for r in ctx["window"]["reqs"]
                  if r.sent is not None and t_lo <= r.sent <= t_hi)
    pairs = [[t - t_lo, (s - w_lo) - (t - t_lo) * 1e9]
             for t, s in zip(sent, spans)] if len(spans) == len(sent) else []
    shift_ns = 0.5 * residual_ns
    if pairs:
        shift_ns = min(0.0, max(residual_ns, max(p[1] for p in pairs)))
    log("trace.clock_anchors", {
        "shift_us": shift_ns / 1e3,
        "submits": [[at, off / 1e3] for at, off in pairs],
    })
    return lambda ts_us: (epoch - t_lo) * 1e9 + ts_us * 1e3 + shift_ns
