"""Which chips a process holds, said two ways: the environment a
launcher gives a child so it opens only its own chips, and the device
summary a process reports once it has opened them.

A TPU chip belongs to one process at a time.  A launcher that starts
several chip-holding children on one host (the worker's task children,
the fleet's serve replicas) must hand each a disjoint set BEFORE the
child imports JAX, and must itself never initialise a JAX backend — a
parent that has touched the chip holds it, and its children then fail
or hang.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Sequence


def chip_visibility_env(ids: Sequence[int]) -> Dict[str, str]:
    """Environment restricting a child process to the host's chips
    ``ids``.  One chip also needs the 1x1x1 process bounds: without
    them libtpu still lays the process out over the host's full chip
    grid and refuses the subset."""
    env = {"TPU_VISIBLE_CHIPS": ",".join(str(i) for i in ids)}
    if len(ids) == 1:
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = "1,1,1"
        env["TPU_PROCESS_BOUNDS"] = "1,1,1"
    return env


def device_summary() -> Dict[str, Any]:
    """The devices this process holds, as JAX reports them — the train
    executor's start line and the serve daemon's ``/healthz`` carry
    it, so whoever launched the process learns the device without
    touching JAX itself."""
    import jax

    devs = jax.devices()
    # a LOCAL device: in a multi-process gang devices()[0] may belong
    # to another process, which cannot be asked.  None on the CPU backend
    mem = jax.local_devices()[0].memory_stats() or {}
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "count": len(devs),
        # which of the host's chips the launcher made visible (None:
        # all of them) — replicas on one host differ here
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        # this process's first device's high-water mark so far, where
        # the backend says
        "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
    }
