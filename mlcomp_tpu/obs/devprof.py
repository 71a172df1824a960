"""Dependency-free xplane reader: device-lane truth without TensorFlow.

``jax.profiler`` writes its capture as an ``*.xplane.pb`` — an
``XSpace`` protobuf (planes -> lines -> events, with per-plane metadata
tables mapping event/stat ids to names).  Every prior consumer in this
repo (``tools/exp_profile_*``) parsed it through
``tensorflow.tsl.profiler.protobuf.xplane_pb2``, which made the proven
xplane methodology unusable anywhere TensorFlow isn't installed — i.e.
the serving container and CI.  The schema the attribution path needs is
tiny and frozen (field numbers are protobuf ABI), so this module walks
the wire format directly: varints, length-delimited submessages, and
the two metadata maps.  No codegen, no imports beyond the stdlib.

Why only device-lane durations: each device line's event
``duration_ps`` is stamped by the device-side tracer, so per-kernel and
per-program durations are what the chip spent, whatever the host was
doing.  Host-lane spans are parsed too (they're the same wire format)
but the attribution helpers aggregate device lanes only.

Schema subset (tensorflow/tsl/profiler/protobuf/xplane.proto):

    XSpace:  planes=1 (XPlane)
    XPlane:  name=2, lines=3 (XLine), event_metadata=4 (map),
             stat_metadata=5 (map)
    XLine:   name=2, timestamp_ns=3, events=4 (XEvent),
             display_name=11
    XEvent:  metadata_id=1, offset_ps=2, duration_ps=3, stats=4
    XEventMetadata: id=1, name=2
    XStatMetadata:  id=1, name=2
    XStat:   metadata_id=1, double=2, uint64=3, int64=4, str=5,
             bytes=6, ref=7 (ref -> stat_metadata name)

Device-lane selection: a TPU capture carries a ``/device:TPU:N``
plane per chip whose ``XLA Ops`` line is the op-level device timeline
(its ``XLA Modules`` and ``Async XLA Ops`` lines overlap it and would
double count); a Pallas kernel appears there under the ``name=`` its
``pallas_call`` gives it (tests/data/tpu_v5e_capture.xplane.pb is such
a capture from a v5e).  CPU captures (``JAX_PLATFORMS=cpu`` — tests,
CI) have no device plane; XLA:CPU's thunk executor shows up as
``tf_XLAPjRtCpuClient/...`` lines on the host plane, which are the
ground truth for "what executed" there, so they are the fallback lane.
Busy time is the INTERVAL UNION across the selected lanes — parallel
lanes (multi-core Eigen, overlapping device streams) must not double
count.
"""

from __future__ import annotations

import glob
import os
from typing import Any, Dict, Iterator, List, Optional, Tuple

# ------------------------------------------------------------ wire walker


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        b = buf[i]
        i += 1
        r |= (b & 0x7F) << s
        if not b & 0x80:
            return r, i
        s += 7
        if s > 70:
            raise ValueError("varint overran 10 bytes (corrupt xplane?)")


def _fields(buf: bytes) -> Iterator[Tuple[int, int, Any]]:
    """Yield (field_number, wire_type, value) over one message body.
    Length-delimited values come back as memoryview-compatible bytes;
    varints as ints; fixed32/64 as raw bytes (unused by this schema
    but skipped correctly so unknown fields never derail the walk)."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        fn, wt = tag >> 3, tag & 7
        if wt == 0:
            v, i = _varint(buf, i)
            yield fn, wt, v
        elif wt == 2:
            ln, i = _varint(buf, i)
            if i + ln > n:
                raise ValueError("length-delimited field overruns buffer")
            yield fn, wt, buf[i:i + ln]
            i += ln
        elif wt == 5:
            yield fn, wt, buf[i:i + 4]
            i += 4
        elif wt == 1:
            yield fn, wt, buf[i:i + 8]
            i += 8
        else:
            raise ValueError(f"unsupported wire type {wt}")


def _i64(v: int) -> int:
    """int64 fields ride as two's-complement varints."""
    return v - (1 << 64) if v >= (1 << 63) else v


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    """proto map<int64, Message> entry: key=1 varint, value=2 bytes."""
    key, val = 0, b""
    for fn, _, v in _fields(buf):
        if fn == 1:
            key = _i64(v)
        elif fn == 2:
            val = v
    return key, val


# ------------------------------------------------------------ model types


class XEvent:
    """One timeline event, metadata already resolved to its name."""

    __slots__ = ("name", "offset_ps", "duration_ps", "stats")

    def __init__(self, name: str, offset_ps: int, duration_ps: int,
                 stats: Optional[Dict[str, Any]] = None):
        self.name = name
        self.offset_ps = int(offset_ps)
        self.duration_ps = int(duration_ps)
        self.stats = stats or {}

    def __repr__(self):  # pragma: no cover - debug aid
        return (f"XEvent({self.name!r}, off={self.offset_ps}, "
                f"dur={self.duration_ps})")


class XLine:
    __slots__ = ("name", "display_name", "timestamp_ns", "events")

    def __init__(self, name: str, display_name: str, timestamp_ns: int,
                 events: List[XEvent]):
        self.name = name
        self.display_name = display_name
        self.timestamp_ns = int(timestamp_ns)
        self.events = events


class XPlane:
    __slots__ = ("name", "lines")

    def __init__(self, name: str, lines: List[XLine]):
        self.name = name
        self.lines = lines


# ---------------------------------------------------------------- parsing


def _parse_stat(buf: bytes, stat_names: Dict[int, str]) -> Tuple[str, Any]:
    mid, val = 0, None
    for fn, _, v in _fields(buf):
        if fn == 1:
            mid = _i64(v)
        elif fn == 2:  # double (fixed64)
            import struct

            val = struct.unpack("<d", v)[0]
        elif fn == 3:
            val = v
        elif fn == 4:
            val = _i64(v)
        elif fn == 5:
            val = bytes(v).decode("utf-8", "replace")
        elif fn == 6:
            val = bytes(v)
        elif fn == 7:  # ref into stat_metadata: the VALUE is a name
            val = stat_names.get(v, str(v))
    return stat_names.get(mid, str(mid)), val


def _parse_event(buf: bytes, ev_names: Dict[int, str],
                 stat_names: Dict[int, str], with_stats: bool) -> XEvent:
    mid = off = dur = 0
    stats: Optional[Dict[str, Any]] = {} if with_stats else None
    for fn, _, v in _fields(buf):
        if fn == 1:
            mid = _i64(v)
        elif fn == 2:
            off = _i64(v)
        elif fn == 3:
            dur = _i64(v)
        elif fn == 4 and with_stats:
            k, sv = _parse_stat(v, stat_names)
            stats[k] = sv
    return XEvent(ev_names.get(mid, str(mid)), off, dur, stats)


def _parse_line(buf: bytes, ev_names: Dict[int, str],
                stat_names: Dict[int, str], with_stats: bool) -> XLine:
    name = disp = ""
    ts_ns = 0
    events: List[XEvent] = []
    for fn, _, v in _fields(buf):
        if fn == 2:
            name = bytes(v).decode("utf-8", "replace")
        elif fn == 11:
            disp = bytes(v).decode("utf-8", "replace")
        elif fn == 3:
            ts_ns = _i64(v)
        elif fn == 4:
            events.append(_parse_event(v, ev_names, stat_names, with_stats))
    return XLine(name, disp or name, ts_ns, events)


def _parse_plane(buf: bytes, with_stats: bool) -> XPlane:
    name = ""
    line_bufs: List[bytes] = []
    ev_names: Dict[int, str] = {}
    stat_names: Dict[int, str] = {}
    for fn, _, v in _fields(buf):
        if fn == 2:
            name = bytes(v).decode("utf-8", "replace")
        elif fn == 3:
            line_bufs.append(v)  # defer: metadata maps may follow lines
        elif fn == 4:
            k, mv = _map_entry(v)
            for mfn, _, m in _fields(mv):  # XEventMetadata.name = 2
                if mfn == 2:
                    ev_names[k] = bytes(m).decode("utf-8", "replace")
        elif fn == 5:
            k, mv = _map_entry(v)
            for mfn, _, m in _fields(mv):  # XStatMetadata.name = 2
                if mfn == 2:
                    stat_names[k] = bytes(m).decode("utf-8", "replace")
    lines = [
        _parse_line(lb, ev_names, stat_names, with_stats)
        for lb in line_bufs
    ]
    return XPlane(name, lines)


def parse_xspace(data: bytes, with_stats: bool = False) -> List[XPlane]:
    """Parse serialized ``XSpace`` bytes into planes.  ``with_stats``
    also decodes per-event XStat key/values (slower; the attribution
    path only needs names and durations, so it defaults off)."""
    return [
        _parse_plane(v, with_stats)
        for fn, wt, v in _fields(data)
        if fn == 1 and wt == 2
    ]


def load_xspace(path: str, with_stats: bool = False) -> List[XPlane]:
    with open(path, "rb") as f:
        return parse_xspace(f.read(), with_stats=with_stats)


def find_xplane(logdir: str) -> str:
    """Newest ``*.xplane.pb`` under a ``jax.profiler`` log directory
    (layout: ``<dir>/plugins/profile/<ts>/<host>.xplane.pb``)."""
    pbs = glob.glob(
        os.path.join(logdir, "**", "*.xplane.pb"), recursive=True
    )
    if not pbs:
        raise FileNotFoundError(f"no *.xplane.pb under {logdir}")
    return max(pbs, key=os.path.getmtime)


# ------------------------------------------------------------ attribution


def short_op(name: str) -> str:
    """Normalize an HLO op label: ``"%fusion.123 = f32[...] ..."`` ->
    ``"fusion"`` (the exp tools' ``short()``, shared)."""
    head = name.split(" = ")[0].lstrip("%")
    return head.rsplit(".", 1)[0]


def device_lines(planes: List[XPlane]) -> List[Tuple[XPlane, XLine]]:
    """The lanes whose durations are trustworthy ground truth:

    - device planes (name contains ``/device:`` or ``TPU``/``GPU``):
      their ``XLA Ops`` op timeline (fall back to every line on the
      plane if the runtime named them differently);
    - otherwise (pure-CPU capture): the host plane's
      ``tf_XLAPjRtCpuClient/...`` lines — XLA:CPU's thunk-executor
      threads, the only lanes recording executed-op spans on that
      backend (the ``tf_XLAEigen/...`` worker lanes carry nothing but
      zero-duration region markers under the installed runtime).
    """
    dev: List[Tuple[XPlane, XLine]] = []
    for p in planes:
        nm = p.name
        if "/device:" in nm or "TPU" in nm or "GPU" in nm:
            ops = [ln for ln in p.lines if ln.name == "XLA Ops"]
            dev.extend((p, ln) for ln in (ops or p.lines))
    if dev:
        return dev
    for p in planes:
        for ln in p.lines:
            if ln.name.startswith("tf_XLAPjRtCpuClient"):
                dev.append((p, ln))
    return dev


def _abs_intervals(
    lines: List[Tuple[XPlane, XLine]]
) -> List[Tuple[int, int, XEvent]]:
    """(start_ps, end_ps, event) on a shared absolute clock: each
    line's ``timestamp_ns`` anchors its events' ps offsets."""
    out = []
    for _, ln in lines:
        base = ln.timestamp_ns * 1000
        for ev in ln.events:
            if ev.duration_ps <= 0:
                continue
            start = base + ev.offset_ps
            out.append((start, start + ev.duration_ps, ev))
    out.sort(key=lambda t: t[0])
    return out


def busy_ms(intervals: List[Tuple[int, int, Any]]) -> float:
    """Interval-union busy time: overlapping lanes (parallel Eigen
    workers, concurrent device streams) count wall once, not per lane."""
    total_ps = 0
    cur_lo = cur_hi = None
    for lo, hi, _ in intervals:
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total_ps += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        elif hi > cur_hi:
            cur_hi = hi
    if cur_hi is not None:
        total_ps += cur_hi - cur_lo
    return total_ps / 1e9


def op_totals(
    lines: List[Tuple[XPlane, XLine]], top: int = 20
) -> List[Dict[str, Any]]:
    """Top device ops by summed duration (normalized names)."""
    tot: Dict[str, float] = {}
    cnt: Dict[str, int] = {}
    for _, ln in lines:
        for ev in ln.events:
            if ev.duration_ps <= 0:
                continue  # instant markers (threadpool region tags)
            k = short_op(ev.name)
            tot[k] = tot.get(k, 0.0) + ev.duration_ps / 1e9
            cnt[k] = cnt.get(k, 0) + 1
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [
        {"name": k, "total_ms": round(ms, 4), "count": cnt[k]}
        for k, ms in ranked
    ]


def attribution(
    planes: List[XPlane],
    wall_ms: Optional[float] = None,
    top_kernels: int = 20,
) -> Dict[str, Any]:
    """Capture-level device/host split: device busy time (interval
    union over the device lanes), the kernel-name breakdown, and —
    when the caller supplies the capture's host wall — the host gap
    (wall the device spent NOT executing: dispatch cost, pipeline
    bubble, admission stall)."""
    lines = device_lines(planes)
    ivs = _abs_intervals(lines)
    dev_ms = busy_ms(ivs)
    out: Dict[str, Any] = {
        "device_time_ms": round(dev_ms, 4),
        "device_events": sum(len(ln.events) for _, ln in lines),
        "device_lanes": sorted({
            f"{p.name}/{ln.display_name}" for p, ln in lines
        })[:16],
        "planes": [p.name for p in planes],
        "kernels": op_totals(lines, top=top_kernels),
    }
    if wall_ms is not None:
        out["wall_ms"] = round(float(wall_ms), 4)
        out["host_gap_ms"] = round(max(float(wall_ms) - dev_ms, 0.0), 4)
    return out


def device_spans_us(
    planes: List[XPlane], limit: int = 768
) -> Tuple[List[Tuple[float, float, str]], int]:
    """Device events as ``(start_us, dur_us, name)`` relative to the
    capture's earliest device event — the shape the flight recorder
    merges as its device track.  Returns ``(spans, dropped)``: when the
    capture holds more than ``limit`` events the LONGEST survive (the
    track is for reading attribution, not archival), and ``dropped``
    says how many were shed."""
    ivs = _abs_intervals(device_lines(planes))
    if not ivs:
        return [], 0
    t0 = ivs[0][0]
    dropped = 0
    if len(ivs) > limit:
        dropped = len(ivs) - limit
        ivs = sorted(ivs, key=lambda t: t[0] - t[1])[:limit]
        ivs.sort(key=lambda t: t[0])
    return [
        ((lo - t0) / 1e6, (hi - lo) / 1e6, ev.name) for lo, hi, ev in ivs
    ], dropped
