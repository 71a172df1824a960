"""Which programs this process compiled, and when.

One ``jax.monitoring`` duration listener a process, registered when this
module is first imported (the decode engine imports it as it is built;
importing it imports jax).  JAX reports a backend compile as it ENDS, on
the thread that ran it, so a ``compile`` instant stamped then lies
inside whatever span of that thread paid for the compile: a flight
recorder that ``watch`` was handed gets one on its ``engine.compile``
track (``seconds``, ``fun``), beside the ``issue``, ``prefill_chunk``,
``insert`` or ``admission_start`` span of the ``engine.loop`` track
that it stalled.  A fetch from the persistent compilation cache is
reported the same way, with the seconds the fetch took.

The totals are the process's, not an engine's: programs are compiled by
warm-up calls on the caller's thread, by the loop, by the prefix
cache's capture worker.
"""

from __future__ import annotations

import threading
import weakref
from typing import Any, Dict

import jax.monitoring

from mlcomp_tpu.utils.trace import Tracer

EVENT = "/jax/core/compile/backend_compile_duration"
TRACK = "engine.compile"

_lock = threading.Lock()
_totals = {"compiled": 0, "compile_seconds": 0.0}
_recorders: "weakref.WeakSet[Tracer]" = weakref.WeakSet()


def watch(recorder: Tracer) -> None:
    """``recorder`` gets a ``compile`` instant for every backend compile
    from now on, for as long as something else keeps it alive."""
    with _lock:
        _recorders.add(recorder)


def totals() -> Dict[str, Any]:
    """{"compiled": programs, "compile_seconds": their seconds}, since
    this module was imported."""
    with _lock:
        return dict(_totals)


def _on_duration(event: str, seconds: float, **kw) -> None:
    if event != EVENT:
        return
    with _lock:
        _totals["compiled"] += 1
        _totals["compile_seconds"] += seconds
        recorders = list(_recorders)
    for rec in recorders:
        rec.instant("compile", track=TRACK, seconds=round(seconds, 6),
                    fun=kw.get("fun_name"))


jax.monitoring.register_event_duration_secs_listener(_on_duration)
