"""Tier-1 runs the tests of the seam between the program and its
yardstick: ``benchmark/tests/test_yardstick.py`` (the arithmetic the
numbers rest on) and ``benchmark/tests/test_loop_spans.py`` (the readers
of the engine's spans and ``stats()``, on hand-made events and on a live
rehearsal-width service), and ``benchmark/tests/test_mixedlen_readers.py``
(the readers of the counts by layer kind and by call class, on
hand-made ops and ``stats()``) and
``benchmark/tests/test_retention_readers.py`` (the retention step's
roofline arithmetic and the readers of the layer's counters) and
``benchmark/tests/test_flash_fwd_calls.py`` (the count of flash forward
calls a backward call, on hand-made ops).  A program PR that renames a span or drops a
``stats()`` key fails here, not as a ``null`` per-layer metric after a
chip run.  The tests are the benchmark's own, imported; nothing under
``benchmark/`` is edited.  Not ``test_correct.py``, ``test_laguna.py`` or
``test_smallthinker.py`` or ``test_brumby.py``: they take minutes (``pytest benchmark/tests``
runs them all)."""

import os

import pytest

from benchmark.tests.conftest import rehearse  # noqa: F401  (a fixture)
from benchmark.tests.test_flash_fwd_calls import *  # noqa: F401,F403
from benchmark.tests.test_loop_spans import *  # noqa: F401,F403
from benchmark.tests.test_mixedlen_readers import *  # noqa: F401,F403
from benchmark.tests.test_retention_readers import *  # noqa: F401,F403
from benchmark.tests.test_yardstick import *  # noqa: F401,F403

_CACHE_OPTIONS = (
    "jax_compilation_cache_dir",
    "jax_enable_compilation_cache",
    "jax_persistent_cache_min_compile_time_secs",
    "jax_persistent_cache_min_entry_size_bytes",
    "jax_compilation_cache_max_size",
)


@pytest.fixture(scope="module", autouse=True)
def _leave_the_compile_cache_as_found():
    """A rehearsal places the persistent compilation cache as an entry
    point does (``benchmark.harness.configure_jax``): the environment
    variable and JAX's options, for the whole process.  This worker runs
    other files after this one, and their child processes inherit the
    environment."""
    import jax

    from mlcomp_tpu.utils.compile_cache import ENV

    env = os.environ.get(ENV)
    options = {name: getattr(jax.config, name) for name in _CACHE_OPTIONS}
    yield
    if env is None:
        os.environ.pop(ENV, None)
    else:
        os.environ[ENV] = env
    for name, value in options.items():
        jax.config.update(name, value)
