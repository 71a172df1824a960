"""What every kind of run shares: the log lines before the result, the
count of programs lowered inside the window, the traced slice, and the
assembly of the one result line."""

from __future__ import annotations

import contextlib
import gc
import json
import threading
import time
from typing import Any, Dict, Optional

from benchmark import cells

# every fresh jit specialization lowers once, whether the executable then
# comes from the compiler or from the persistent cache
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def configure_jax(cell) -> str:
    """The persistent compilation cache, for every entry point: at the
    program's own location, every program kept, nothing evicted."""
    try:
        from mlcomp_tpu.utils.compile_cache import place_compile_cache
    except ImportError as e:
        raise SystemExit(f"the system under test is not in this checkout: {e}")
    cache_dir = place_compile_cache()

    import jax

    if cell.rehearsal:
        # XLA:CPU executables do not reload cleanly across machines
        jax.config.update("jax_enable_compilation_cache", False)
    # everything the window runs is found in the cache by the next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # no eviction: the train step alone is a 144 MB executable, and a cap
    # near that size (the chip tool's machine sets 192 MiB) evicts every
    # other entry each time it is written, so that no run ever hits
    jax.config.update("jax_compilation_cache_max_size", -1)
    return cache_dir


def log(name: str, value) -> None:
    """One ``name value`` line on standard output, before the result."""
    if isinstance(value, float):
        value = repr(value)
    elif not isinstance(value, str):
        value = json.dumps(value)
    print(f"{name} {value}", flush=True)


_T0 = time.perf_counter()


def mark(stage: str) -> None:
    """``at.<stage> <seconds since the harness was imported>``: where a
    run's time went, stage by stage, on lines before the result."""
    log(f"at.{stage}", time.perf_counter() - _T0)


class LowerCounter:
    """Counts programs lowered while armed: inside the measured window
    the count must stay 0 (nothing compiles there).  It also keeps, for
    the set-up split, how many programs were lowered in all, how many the
    persistent cache served or missed, and the seconds spent tracing and
    in the compiler."""

    def __init__(self):
        import jax.monitoring

        self.n = 0
        self.armed = False
        self.totals = {"lowered": 0, "cache_hits": 0, "cache_misses": 0,
                       "trace_s": 0.0, "backend_compile_s": 0.0}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == _LOWER_EVENT:
            self.totals["lowered"] += 1
            if self.armed:
                self.n += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.totals["trace_s"] += duration
        elif event == "/jax/core/compile/backend_compile_duration":
            self.totals["backend_compile_s"] += duration

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.totals["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.totals["cache_misses"] += 1

    @contextlib.contextmanager
    def window(self):
        self.n, self.armed = 0, True
        try:
            yield self
        finally:
            self.armed = False


class GcWatch:
    """The benchmark process's own collector, kept out of the window.

    Set-up traces dozens of layers of kernels and leaves millions of
    long-lived objects; a full collection walking them stops the one
    thread that feeds the chip.  ``settle()`` collects once and freezes
    what survived (``gc.freeze``: those objects are never walked again;
    whatever the window allocates is still collected).  While armed it
    adds up the seconds the collector ran, so that a stall in a window
    can be told from one (``gc_in_window_s`` on a line before the
    result)."""

    def __init__(self):
        self.seconds = 0.0
        self.collections = 0
        self._t0 = None

    def _on(self, phase: str, info) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.seconds += time.perf_counter() - self._t0
            self.collections += 1
            self._t0 = None

    def settle(self) -> None:
        gc.collect()
        gc.freeze()

    @contextlib.contextmanager
    def window(self):
        gc.callbacks.append(self._on)
        try:
            yield self
        finally:
            gc.callbacks.remove(self._on)
            gc.unfreeze()
            log("gc_in_window_s", {"seconds": self.seconds,
                                   "collections": self.collections})


class TracedSlice:
    """``--trace 1``: a profiler capture of a steady slice of the window,
    started and stopped from a side thread so that the load generator's
    thread does nothing but generate.  The slice is marked by a
    ``bench.slice`` span on the profiler's own clock."""

    def __init__(self, enabled: bool, start_s: float, length_s: float):
        self.enabled = enabled
        self.start_s, self.length_s = float(start_s), float(length_s)
        self.data = None  # the capture, as ``jax.profiler.ProfileData``
        self.t_lo = self.t_hi = None
        self.error: Optional[BaseException] = None
        self.stop_s = 0.0
        self._thread: Optional[threading.Thread] = None

    @classmethod
    def steady(cls, enabled: bool, seconds: float,
               mix: Optional[Dict[str, Any]] = None) -> "TracedSlice":
        """From 40% of the window on, for the mix's ``trace_slice_s``
        seconds, or where the mix says nothing a fifth of the window and
        at most 4 s.  Never more than that fifth: the capture is stopped
        and handed over while the rest of the window runs.  What a slice
        costs follows from the ops it holds, not from its seconds: the
        serve cells run some 690,000 device ops a second, and on the v5e
        the profiler takes ~28 s to stop and hand over one second of them
        (PERF.md), time a run has to have inside its limit."""
        length = min(4.0, 0.2 * seconds)
        if mix is not None and "trace_slice_s" in mix:
            length = min(float(mix["trace_slice_s"]), 0.2 * seconds)
        return cls(enabled, 0.4 * seconds, length)

    def annotate(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax.profiler

        return jax.profiler.TraceAnnotation(name)

    def _run(self, t0: float) -> None:
        import jax
        import jax.profiler
        # The session object itself, not ``jax.profiler.stop_trace``: that
        # one also writes the capture out, as ``.xplane.pb`` and as gzipped
        # trace-viewer JSON, which nothing here reads; with it one traced
        # second of a serve cell's ~700,000 ops took 114 s to stop and the
        # run outlasted its limit (PERF.md, refusal round).  The session's
        # own stop hands the capture over in memory; nothing is written.
        from jax._src.lib import _profiler

        try:
            time.sleep(max(0.0, t0 + self.start_s - time.perf_counter()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.devices()  # the backend is up before the device tracer is
            session = _profiler.ProfilerSession(opts)
            try:
                self.t_lo = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.slice"):
                    time.sleep(self.length_s)
                self.t_hi = time.perf_counter()
            finally:
                t = time.perf_counter()
                self.data = session.stop_and_get_profile_data()
                self.stop_s = time.perf_counter() - t
        except BaseException as e:  # surfaced by load()
            self.error = e

    def start(self, t0: float) -> None:
        if not self.enabled:
            return
        self._thread = threading.Thread(target=self._run, args=(t0,))
        self._thread.start()

    def load(self):
        """Wait for the capture and reduce it; None when tracing is off."""
        if not self.enabled:
            return None
        from benchmark.xplane import Trace

        t0 = time.perf_counter()
        self._thread.join()
        if self.error is not None:
            raise self.error
        t1 = time.perf_counter()
        trace = Trace(self.data)
        self.data = None
        # what the traced run pays beyond an untraced one: the profiler's
        # stop (it gathers the capture from the chip), how long the run
        # then still waited for it, and the reading
        log("trace.cost_s", {"stop": self.stop_s, "waited": t1 - t0,
                             "read": time.perf_counter() - t1,
                             "device_ops": sum(map(len, trace.ops.values()))})
        return trace


# every number a run compared for ``correct``, beside its limit: the
# result line carries them under ``compared`` (its last key) and
# ``run.py`` prints them as the last lines of standard error
_COMPARED: Dict[str, Dict[str, float]] = {}


def compare(name: str, value: float, limit: float) -> bool:
    """One number against its limit, printed and kept for the result."""
    good = bool(value <= limit)
    _COMPARED[name] = {"value": value, "limit": limit}
    log(f"correct.{name}", {"value": value, "limit": limit, "ok": good})
    return good


def judge(readings: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Each of the mix's limits against its reading; all must hold."""
    held = [compare(name, readings[name], limit)
            for name, limit in limits.items()]
    return all(held)


def result_line(cell, dev: Dict[str, Any], trace: bool, correct: bool,
                attempted: int, failed: int, e2e: Dict[str, float],
                layer_ctx: Optional[Dict[str, Any]],
                memory_peak: int, memory_steady: int) -> Dict[str, Any]:
    """``memory_peak_bytes`` is the allocator's peak over the whole
    process on the fullest chip, set-up's transients and every program's
    temporaries included (the only true peak: it cannot be reset).
    ``memory_steady_bytes`` is what was in use between programs at the
    window's open and close: the resident state a deployment keeps."""
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": int(memory_peak),
              "memory_steady_bytes": int(memory_steady)}
    metrics: Dict[str, Any] = {}
    out: Dict[str, Any] = {"correct": bool(correct),
                           "attempted": int(attempted),
                           "failed": int(failed)}
    if not trace:
        for m in cell.end_to_end():
            if m["name"] not in e2e:
                raise RuntimeError(
                    f"cell {cell.name} did not measure {m['name']}"
                )
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        tr = layer_ctx["trace"]
        if tr is not None and tr.devices:
            log("trace.programs", tr.module_totals())
            log("trace.host_spans", len(tr.host))
            device["busy_s"] = tr.busy_s()
            device["window_s"] = tr.window_s(layer_ctx["slice_s"])
            out["breakdown"] = tr.breakdown()
        took = {}
        for m in cell.per_layer():
            reader = cells.layer_reader(m["name"])
            if reader is None:
                raise RuntimeError(f"no reader for {m['name']}")
            t0 = time.perf_counter()
            value = reader(m["name"], layer_ctx)
            took[m["name"]] = time.perf_counter() - t0
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        log("trace.readers_s", took)
        mark("layer_metrics_read")
    if cell.rehearsal:
        # a CPU rehearsal never prints a number under a device metric's name
        out["rehearsal_metrics"] = metrics
        metrics = {}
    out["metrics"] = metrics
    out["device"] = device
    out["compared"] = dict(_COMPARED)
    _COMPARED.clear()  # one process may run more than one cell (tests)
    return out
