"""obs/devprof: the dependency-free xplane reader.

Three layers of evidence:

- hand-encoded wire bytes (a tiny XSpace built field by field) decode
  to exactly the planes/lines/events/names written — the walker's
  varint/length-delimited/map handling is pinned without any profiler
  in the loop;
- the SHIPPED capture fixtures parse and attribute: device lanes
  found, busy time positive, kernel names resolved —
  ``tests/data/cpu_capture.xplane.pb`` (a real ``jax.profiler`` CPU
  capture under the installed JAX) and
  ``tests/data/tpu_v5e_capture.xplane.pb`` (the ``/device:TPU:0``
  plane of a capture taken on a v5e chip: flash forward, int8-KV
  decode attention and the int8 matmul at the 1.2B shapes);
- a LIVE capture produced in-test under ``JAX_PLATFORMS=cpu`` parses
  the same way — the fixture can't go stale silently;
- and the package ships no TensorFlow import anywhere (the whole point
  of the reader).
"""

import glob
import os
import struct

import pytest

from mlcomp_tpu.obs import devprof

FIXTURE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "data",
    "cpu_capture.xplane.pb",
)


# --------------------------------------------------- wire-format encoding


def _vint(v: int) -> bytes:
    out = b""
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out += bytes([b | 0x80])
        else:
            return out + bytes([b])


def _field(fn: int, payload: bytes) -> bytes:
    return _vint((fn << 3) | 2) + _vint(len(payload)) + payload


def _vfield(fn: int, v: int) -> bytes:
    return _vint(fn << 3) + _vint(v)


def _tiny_xspace() -> bytes:
    """One device plane, one "XLA Ops" line at timestamp 1000 ns with
    two events (ids 7 and 9), metadata mapping them to op names; plus
    a host plane the device-lane selector must skip."""
    ev7 = _vfield(1, 7) + _vfield(2, 5_000) + _vfield(3, 2_000_000_000)
    ev9 = (_vfield(1, 9) + _vfield(2, 2_500_000_000)
           + _vfield(3, 1_000_000_000))
    line = (
        _field(2, b"XLA Ops") + _vfield(3, 1000)
        + _field(4, ev7) + _field(4, ev9)
    )
    md7 = _field(2, _vfield(1, 7) + _field(2, b"%fusion.42 = f32[8]"))
    md9 = _field(2, _vfield(1, 9) + _field(2, b"%copy.7 = s32[4]"))
    plane = (
        _field(2, b"/device:TPU:0") + _field(3, line)
        + _field(4, _vfield(1, 7) + md7)
        + _field(4, _vfield(1, 9) + md9)
    )
    host_line = _field(2, b"python") + _field(
        4, _vfield(1, 1) + _vfield(2, 0) + _vfield(3, 500_000)
    )
    host = _field(2, b"/host:CPU") + _field(3, host_line)
    return _field(1, plane) + _field(1, host)


def test_wire_walker_decodes_handwritten_xspace():
    planes = devprof.parse_xspace(_tiny_xspace())
    assert [p.name for p in planes] == ["/device:TPU:0", "/host:CPU"]
    dev = planes[0]
    assert [ln.name for ln in dev.lines] == ["XLA Ops"]
    line = dev.lines[0]
    assert line.timestamp_ns == 1000
    assert [(e.name, e.offset_ps, e.duration_ps) for e in line.events] == [
        ("%fusion.42 = f32[8]", 5_000, 2_000_000_000),
        ("%copy.7 = s32[4]", 2_500_000_000, 1_000_000_000),
    ]


def test_device_lane_selection_prefers_device_plane():
    planes = devprof.parse_xspace(_tiny_xspace())
    lanes = devprof.device_lines(planes)
    assert [(p.name, ln.name) for p, ln in lanes] == [
        ("/device:TPU:0", "XLA Ops")
    ]


def test_attribution_on_handwritten_xspace():
    planes = devprof.parse_xspace(_tiny_xspace())
    att = devprof.attribution(planes, wall_ms=10.0)
    # spans [5e3, ~2e9] and [2.5e9, 3.5e9] ps do not overlap:
    # union = 3.0 ms exactly
    assert att["device_time_ms"] == pytest.approx(3.0, abs=1e-4)
    assert att["host_gap_ms"] == pytest.approx(7.0, abs=1e-4)
    names = [k["name"] for k in att["kernels"]]
    assert names == ["fusion", "copy"]  # normalized, duration-ranked


def test_busy_ms_merges_overlapping_lanes():
    # ps intervals: [0, 1ms] and [0.5ms, 2ms] overlap -> 2ms union,
    # plus a disjoint [3ms, 4ms] -> 3ms total
    ivs = [(0, 1_000_000_000, None), (500_000_000, 2_000_000_000, None),
           (3_000_000_000, 4_000_000_000, None)]
    assert devprof.busy_ms(ivs) == pytest.approx(3.0)


def test_varint_overrun_raises():
    with pytest.raises(ValueError):
        devprof.parse_xspace(_field(1, b"\xff" * 11))


def test_truncated_length_delimited_raises():
    bad = _vint((1 << 3) | 2) + _vint(64) + b"short"
    with pytest.raises(ValueError):
        devprof.parse_xspace(bad)


# ------------------------------------------------------- capture fixtures


def test_shipped_cpu_fixture_parses_and_attributes():
    planes = devprof.load_xspace(FIXTURE)
    assert any("/host:CPU" in p.name for p in planes)
    lanes = devprof.device_lines(planes)
    assert lanes, "no device-equivalent lanes found in the CPU capture"
    att = devprof.attribution(planes, wall_ms=1e4)
    assert att["device_time_ms"] > 0
    assert att["kernels"], "no kernels aggregated"
    # the capture traced one jitted x@x+1: its fusion must be visible
    assert any("fusion" in k["name"] for k in att["kernels"])
    spans, dropped = devprof.device_spans_us(planes)
    assert spans and dropped == 0
    t0s = [s[0] for s in spans]
    assert min(t0s) == 0.0  # spans are capture-relative
    assert all(d > 0 for _, d, _ in spans)


def test_shipped_tpu_fixture_selects_xla_ops_and_names_kernels():
    """The real-chip capture: the device lane is the TPU plane's
    "XLA Ops" line (not "XLA Modules"/"Async XLA Ops", which would
    double count), and the Pallas kernels appear under the names their
    pallas_call sites give them — what GET /profile and chip_smoke.py
    match on."""
    planes = devprof.load_xspace(os.path.join(
        os.path.dirname(FIXTURE), "tpu_v5e_capture.xplane.pb"
    ))
    lanes = devprof.device_lines(planes)
    assert [(p.name, ln.name) for p, ln in lanes] == [
        ("/device:TPU:0", "XLA Ops")
    ]
    att = devprof.attribution(planes)
    assert att["device_time_ms"] == pytest.approx(1.8745, abs=1e-3)
    by_name = {k["name"]: k for k in att["kernels"]}
    for kernel in ("flash_fwd_kernel_tri", "decode_attention",
                   "quant_matmul"):
        assert by_name[kernel]["total_ms"] > 0, kernel


def test_live_cpu_capture_parses(tmp_path):
    """End to end under JAX_PLATFORMS=cpu (conftest pins it): produce a
    fresh xplane with jax.profiler, then read it back with the
    dependency-free walker — the acceptance path, no TF anywhere."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = devprof.find_xplane(str(tmp_path))
    planes = devprof.load_xspace(path)
    assert planes
    att = devprof.attribution(planes)
    assert att["device_time_ms"] > 0
    assert att["device_events"] > 0


def test_find_xplane_missing_dir_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        devprof.find_xplane(str(tmp_path))


def test_no_tensorflow_import_in_package_or_tools():
    """The reader exists so nothing needs tensorflow.tsl: any import of
    tensorflow anywhere in mlcomp_tpu/ or tools/ is a regression."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    offenders = []
    for sub in ("mlcomp_tpu", "tools"):
        for path in glob.glob(
            os.path.join(root, sub, "**", "*.py"), recursive=True
        ):
            with open(path) as fh:
                for i, ln in enumerate(fh, 1):
                    s = ln.strip()
                    if s.startswith(("import tensorflow",
                                     "from tensorflow")):
                        offenders.append(f"{path}:{i}")
    assert not offenders, f"tensorflow imports found: {offenders}"


def test_parse_with_stats_resolves_refs():
    """XStat decoding: str values pass through, ref values resolve via
    stat_metadata."""
    stat_str = _vfield(1, 3) + _field(5, b"hello")
    stat_ref = _vfield(1, 4) + _vfield(7, 5)
    ev = (_vfield(1, 7) + _vfield(2, 0) + _vfield(3, 10)
          + _field(4, stat_str) + _field(4, stat_ref))
    line = _field(2, b"XLA Ops") + _field(4, ev)
    smd3 = _field(2, _vfield(1, 3) + _field(2, b"note"))
    smd4 = _field(2, _vfield(1, 4) + _field(2, b"kind"))
    smd5 = _field(2, _vfield(1, 5) + _field(2, b"fused_kind"))
    plane = (
        _field(2, b"/device:TPU:0") + _field(3, line)
        + _field(4, _vfield(1, 7) + _field(
            2, _vfield(1, 7) + _field(2, b"op")))
        + _field(5, _vfield(1, 3) + smd3)
        + _field(5, _vfield(1, 4) + smd4)
        + _field(5, _vfield(1, 5) + smd5)
    )
    planes = devprof.parse_xspace(_field(1, plane), with_stats=True)
    ev = planes[0].lines[0].events[0]
    assert ev.stats == {"note": "hello", "kind": "fused_kind"}
