"""The device a run is measured on, and the one table of peaks."""

from __future__ import annotations

from typing import Any, Dict

# Published peaks of one chip, keyed by ``device_kind`` as JAX reports
# it.  A device that is not here is an error, never a default.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' system "
                  "architecture page",
    },
}


def describe(chips: int, rehearsal: bool) -> Dict[str, Any]:
    """Platform, kind and count as JAX reports them.  Exits non-zero
    when the cell cannot be measured here: no accelerator, fewer chips
    than it asks for, or a chip without published peaks.  A rehearsal
    is the reverse: it runs on the CPU only."""
    import jax

    devs = jax.devices()
    dev = devs[0]
    if rehearsal:
        if dev.platform != "cpu":
            raise SystemExit(
                "a rehearsal runs tiny widths on the CPU; on an "
                "accelerator run the cell itself"
            )
        return {"platform": "cpu", "kind": dev.device_kind,
                "count": len(devs), "peaks": None}
    if dev.platform == "cpu":
        raise SystemExit(
            "JAX found no accelerator (platform 'cpu'): a CPU run of a "
            "cell is not a measurement.  Rehearse with --rehearsal 1"
        )
    if len(devs) < chips:
        raise SystemExit(
            f"the cell needs {chips} chip(s); JAX found {len(devs)}"
        )
    if dev.device_kind not in PEAKS:
        raise SystemExit(
            f"no published peaks for device_kind {dev.device_kind!r} "
            f"(known: {sorted(PEAKS)}): add it to benchmark/device.py "
            "with its source"
        )
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs), "peaks": PEAKS[dev.device_kind]}


def memory(chips: int) -> Dict[str, int]:
    """Peak and current bytes on the fullest of the chips in use."""
    import jax

    peak = cur = limit = 0
    for d in jax.devices()[:chips]:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
        cur = max(cur, int(st.get("bytes_in_use", 0)))
        limit = max(limit, int(st.get("bytes_limit", 0)))
    return {"peak": peak, "in_use": cur, "limit": limit}
