"""Tests of the benchmark's own yardstick; tiny widths, CPU only.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

Not collected by the repo's tier-1 command (which names ``tests/``)."""

import contextlib
import io
import json
import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def run_cell(*argv):
    """Drive ``benchmark.run`` in this process as a CPU rehearsal; returns
    (lines before the result as {name: parsed value}, the result)."""
    from benchmark import run

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main([*argv, "--rehearsal", "1"])
    assert rc == 0
    lines = [ln for ln in buf.getvalue().splitlines() if ln.strip()]
    seen = {}
    for ln in lines[:-1]:
        name, _, rest = ln.partition(" ")
        try:
            seen[name] = json.loads(rest)
        except json.JSONDecodeError:
            seen[name] = rest
    return seen, json.loads(lines[-1])


@pytest.fixture(scope="session")
def rehearse():
    return run_cell
