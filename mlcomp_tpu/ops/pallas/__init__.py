"""Pallas TPU kernels, and the one place that asks which backend they
run on: compiled by Mosaic on ``tpu``, interpreted on ``cpu`` (the test
backend).  Nothing else is recognised, and a backend that cannot be
queried raises — a kernel that silently ran interpreted, or a route
that silently took a reference path, would hide the device."""

import jax


def on_tpu() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"unsupported JAX backend {backend!r}: the kernels compile "
            "on 'tpu' and run interpreted on 'cpu'"
        )
    return backend == "tpu"


def interpret_default() -> bool:
    """The ``interpret=None`` resolution every kernel entry point
    shares."""
    return not on_tpu()
