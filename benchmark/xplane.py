"""Reduce a ``jax.profiler`` trace to what the per-layer metrics read.

The reduction follows ``mlcomp_tpu/obs/devprof.py`` (read against a real
v5e capture in PR 22) and is a copy by intent: the yardstick must not
move with the program.  It reads the capture (handed over in memory by
the profiler's session, or an ``.xplane.pb`` file) through
``jax.profiler.ProfileData``: a TPU capture has one ``/device:TPU:N``
plane per chip whose ``XLA Ops`` line is the op timeline (``XLA
Modules`` carries one span per executed program and overlaps it; ``Async
XLA Ops`` would double count), and a host plane whose thread lines carry
the ``TraceAnnotation`` spans the benchmark's own driver code writes.

Busy time is the union of op intervals, per chip, averaged over chips.
"""

from __future__ import annotations

import os
import re
from typing import Any, Dict, List, Optional, Tuple

Span = Tuple[str, float, float]  # name, start_ns, end_ns

# the decode engine's programs on the device: the plain K-step dispatch and
# the fused prefill+decode dispatch (engine.py's jitted ``dispatch``/``fused``)
DISPATCH_PROGRAMS = r"^jit_(dispatch|fused)\b"
PLAIN_DISPATCH = r"^jit_dispatch\b"


def short_op(name: str) -> str:
    """``"%fusion.123 = f32[...] ..."`` -> ``"fusion"``."""
    head = name.split(" = ")[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def union_ns(spans: List[Span]) -> float:
    total, lo, hi = 0.0, None, None
    for _, s, e in sorted(spans, key=lambda t: t[1]):
        if hi is None or s > hi:
            if hi is not None:
                total += hi - lo
            lo, hi = s, e
        elif e > hi:
            hi = e
    if hi is not None:
        total += hi - lo
    return total


def gaps(spans: List[Span]) -> List[Tuple[float, float]]:
    """Idle intervals (start_ns, end_ns) between consecutive busy runs."""
    out, hi = [], None
    for _, s, e in sorted(spans, key=lambda t: t[1]):
        if hi is not None and s > hi:
            out.append((hi, s))
        hi = e if hi is None else max(hi, e)
    return out


class Trace:
    """Device ops, device programs and the benchmark's host spans of one
    capture, clipped to the span named ``bench.slice`` when the host
    plane has it."""

    def __init__(self, source, slice_name: str = "bench.slice"):
        """``source``: the path of an ``.xplane.pb``, or the capture
        itself as ``jax.profiler.ProfileData``."""
        from jax.profiler import ProfileData

        data = source
        if isinstance(source, (str, os.PathLike)):
            data = ProfileData.from_file(os.fspath(source))
        self.ops: Dict[str, List[Span]] = {}      # per device plane
        self.modules: Dict[str, List[Span]] = {}
        self.host: List[Span] = []
        for plane in data.planes:
            name = plane.name
            if name.startswith("/device:") and "TPU" in name.upper():
                for line in plane.lines:
                    if line.name in ("XLA Ops", "XLA Modules"):
                        dst = self.ops if line.name == "XLA Ops" \
                            else self.modules
                        dst.setdefault(name, []).extend(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events if e.duration_ns > 0
                        )
            elif name.startswith("/host:") and name != "/host:metadata":
                for line in plane.lines:
                    self.host.extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if e.name.startswith("bench.")
                    )
        self.window: Optional[Tuple[float, float]] = None
        cut = [s for s in self.host if s[0] == slice_name]
        if cut:
            lo, hi = cut[0][1], cut[0][2]
            self.window = (lo, hi)
            for table in (self.ops, self.modules):
                for k, spans in table.items():
                    table[k] = [
                        (n, max(s, lo), min(e, hi))
                        for n, s, e in spans if e > lo and s < hi
                    ]

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)

    def busy_s(self) -> float:
        """Seconds an op ran on the device, averaged over the chips."""
        if not self.ops:
            return 0.0
        return sum(union_ns(v) for v in self.ops.values()) / len(self.ops) / 1e9

    def window_s(self, fallback_s: float) -> float:
        if self.window is not None:
            return (self.window[1] - self.window[0]) / 1e9
        return float(fallback_s)

    def op_totals(self) -> Dict[str, Tuple[float, int]]:
        """Short op name -> (seconds summed over chips / chips, count)."""
        tot: Dict[str, List[float]] = {}
        n = max(1, len(self.ops))
        for spans in self.ops.values():
            for name, s, e in spans:
                t = tot.setdefault(short_op(name), [0.0, 0])
                t[0] += (e - s) / 1e9 / n
                t[1] += 1
        return {k: (v[0], int(v[1])) for k, v in tot.items()}

    def module_totals(self) -> Dict[str, Tuple[float, int]]:
        """Program name (hash stripped) -> (seconds, runs) on the first chip."""
        out: Dict[str, List[float]] = {}
        if self.modules:
            for name, s, e in self.modules[sorted(self.modules)[0]]:
                t = out.setdefault(re.sub(r"\(\d+\)$", "", name), [0.0, 0])
                t[0] += (e - s) / 1e9
                t[1] += 1
        return {k: (v[0], int(v[1])) for k, v in out.items()}

    def module_spans(self, pattern: str) -> List[Span]:
        """Program spans on the first chip whose name matches."""
        if not self.modules:
            return []
        rx = re.compile(pattern)
        first = self.modules[sorted(self.modules)[0]]
        return [s for s in first if rx.search(s[0])]

    def kernel_events(self, match) -> List[Span]:
        """Op events on every chip that ``match(full_name)`` accepts."""
        return [s for v in self.ops.values() for s in v if match(s[0])]

    def breakdown(self, top: int = 10) -> Dict[str, Any]:
        """The contract's ``breakdown``: the ops that took most time, and
        the longest idle gaps named by the benchmark's host span that
        covers most of each."""
        # control-flow ops span their bodies, whose ops are listed too
        ops = sorted(
            ((k, v[0]) for k, v in self.op_totals().items()
             if k not in ("while", "conditional", "call")),
            key=lambda kv: -kv[1],
        )[:top]
        idle: Dict[str, float] = {}
        if self.ops:
            first = self.ops[sorted(self.ops)[0]]
            marks = [h for h in self.host if h[0] != "bench.slice"]
            for lo, hi in sorted(gaps(first), key=lambda g: g[0] - g[1])[:200]:
                best, cover = "host:unnamed", 0.0
                for name, s, e in marks:
                    c = min(e, hi) - max(s, lo)
                    if c > cover:
                        best, cover = name, c
                idle[best] = idle.get(best, 0.0) + (hi - lo) / 1e9
        gaps_named = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
        return {
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps_named],
        }


HLO_TYPES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}
_SHAPE = re.compile(r"\b([a-z]+[0-9a-z]*)\[([0-9,]*)\]")


def hlo_shapes(op_text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """Every ``dtype[dims]`` of an op's HLO text, in order: the result
    first, then the operands."""
    out = []
    for dt, dims in _SHAPE.findall(op_text):
        if dt in HLO_TYPES:
            out.append((dt, tuple(int(x) for x in dims.split(",") if x)))
    return out


def nbytes(shape: Tuple[str, Tuple[int, ...]]) -> int:
    n = HLO_TYPES[shape[0]]
    for d in shape[1]:
        n *= d
    return n


def roofline_share(trace: "Trace", kernel, peaks: Dict[str, float],
                   ctx: Dict[str, Any]) -> Optional[float]:
    """A kernel's share of its roofline, in percent: the least time the
    chip could take for every traced call (the larger of operations over
    peak rate and bytes over peak bandwidth, from ``kernel.cost``) over
    the time the calls took.  None when the trace has no such call."""
    events = trace.kernel_events(kernel.match)
    if not events:
        return None
    least = took = 0.0
    for name, s, e in events:
        flops, nbytes_ = kernel.cost(name, ctx)
        least += max(flops / peaks["bf16_flops"],
                     nbytes_ / peaks["hbm_bytes_per_s"])
        took += (e - s) / 1e9
    return 100.0 * least / took if took > 0 else None
