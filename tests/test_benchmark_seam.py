"""Tier-1 runs the tests of the seam between the program and its
yardstick: ``benchmark/tests/test_yardstick.py`` (the arithmetic the
numbers rest on) and ``benchmark/tests/test_loop_spans.py`` (the readers
of the engine's spans and ``stats()``, on hand-made events and on a live
rehearsal-width service), and ``benchmark/tests/test_mixedlen_readers.py``
(the readers of the counts by layer kind and by call class, on
hand-made ops and ``stats()``) and
``benchmark/tests/test_retention_readers.py`` (the retention step's
roofline arithmetic and the readers of the layer's counters) and
``benchmark/tests/test_kimi_linear_readers.py`` (the KDA step's and the
latent decode's roofline arithmetic and the readers of the two layers'
counters) and
``benchmark/tests/test_lfm2_moe_readers.py`` (the decode attention's
roofline at a head narrower than a lane tile and the readers of the
bytes of cache read a token) and
``benchmark/tests/test_flash_fwd_calls.py`` (the count of flash forward
calls a backward call, on hand-made ops) and
``benchmark/tests/test_lane_readers.py`` (the readers of the admission
lane's books: the row ledger, the ``engine.lane`` track, the ``admit``
and ``inserted`` instants' arguments, on hand-made events and ``stats()``
pairs and on a live rehearsal-width service) and
``benchmark/tests/test_longcat_flash_readers.py`` (the latent decode's
roofline at 64 heads in a stack with no KDA layer, the zero experts'
share, the chunk form's ops by their shapes).  A program PR that renames a span or drops a
``stats()`` key fails here, not as a ``null`` per-layer metric after a
chip run.  The tests are the benchmark's own, imported; nothing under
``benchmark/`` is edited.  Not ``test_correct.py``, ``test_laguna.py`` or
``test_smallthinker.py``, ``test_brumby.py``, ``test_kimi_linear.py`` or
``test_lfm2_moe.py``: they take minutes (``pytest benchmark/tests``
runs them all).  Two imported tests are redefined below, and their
docstrings say why."""

import os

import pytest

from benchmark.tests.conftest import rehearse  # noqa: F401  (a fixture)
from benchmark.tests.test_flash_fwd_calls import *  # noqa: F401,F403
from benchmark.tests.test_kimi_linear_readers import *  # noqa: F401,F403
from benchmark.tests.test_lane_readers import *  # noqa: F401,F403
from benchmark.tests.test_lfm2_moe_readers import *  # noqa: F401,F403
from benchmark.tests.test_longcat_flash_readers import *  # noqa: F401,F403
from benchmark.tests.test_loop_spans import *  # noqa: F401,F403
from benchmark.tests.test_mixedlen_readers import *  # noqa: F401,F403
from benchmark.tests.test_retention_readers import *  # noqa: F401,F403
from benchmark.tests.test_yardstick import *  # noqa: F401,F403



def test_the_entries_name_the_cell_and_its_files():  # noqa: F811
    """``test_retention_readers.py``'s test of this name, but for its
    count: it pins the entries that list ``continuation-offline`` alone at
    the eight of PR 37, and PR 39 added four (the row ledger's two
    shares, the lane's busy share and boundaries).  A PR may add entries
    to ``BENCHMARK.json`` and may not edit a file under ``benchmark/``,
    so tier-1 holds the eight to what they were, asks every later entry
    for a reader, and leaves the count to the next ``benchmark`` PR."""
    import json

    from benchmark import cells

    spec = cells.benchmark_spec()
    mine = [m for m in spec["per_layer"]
            if m.get("workloads") == ["continuation-offline"]]
    assert [m["name"] for m in mine[:8]] == [
        "fused_dispatch_ms.continuation", "dispatch_gap_ms.continuation",
        "device_idle.continuation", "host_ms_per_dispatch.continuation",
        "admit_boundary_idle.continuation",
        "retention_time_share.continuation", "retention_step_roofline",
        "state_bytes_per_token.continuation"]
    assert all(m["moves"] == "serve_tokens_per_s" for m in mine)
    assert all(cells.layer_reader(m["name"]) is not None for m in mine)
    cell = cells.Cell("continuation-offline")
    assert cell.config["reference"] == "brumby" and cell.chips == 1
    assert [c["reduced"] for c in spec["configs"]
            if c["name"] == "brumby-14b-serve"] == [["num_hidden_layers"]]
    with open(cells.ROOT / "benchmark/configs/brumby-14b-serve.json") as f:
        assert json.load(f)["num_hidden_layers"] == 5


def test_kimi_rows_tokens_and_bytes_a_token_are_run_deltas():  # noqa: F811
    """``test_kimi_linear_readers.py``'s test of this name, but for its
    count: it pins the entries of six readers at the six of PR 41, and
    PR 47 gave ``latent_attn_time_share`` a second cell
    (``.docqa``).  Tier-1 holds ``reasoning-offline``'s six to what
    they were, asks every entry of those readers, the new one too, for
    no number and no raise from a program without the counts, and
    leaves the count to the next ``benchmark`` PR."""
    from benchmark import cells
    from benchmark.layer_metrics.cache_counts import delta
    from benchmark.tests import test_kimi_linear_readers as kimi

    before = kimi._stats(rows=400.0, state=400.0 * 1e6, tokens=9e4,
                         fetched=9e4 * 1280, steps=20, emitted=2100)
    after = kimi._stats(rows=400.0 + 380000.0, steps=1020,
                        state=(400.0 + 380000.0) * 1e6, tokens=9e4 + 2.85e8,
                        fetched=(9e4 + 3.135e8) * 1280, emitted=2100 + 95000)
    got = delta(kimi._ctx(before, after))
    assert got["kda"]["state_rows"] == 380000.0 and got["steps"] == 1000
    name = "cache_bytes_per_token.reasoning"
    assert cells.layer_reader(name)(name, kimi._ctx(before, after)) \
        == pytest.approx((380000.0 * 1e6 + 3.135e8 * 1280) / 95000 / 1e6)
    entries = [m for m in cells.benchmark_spec()["per_layer"]
               if m["name"].split(".")[0] in (
                   "cache_bytes_per_token", "latent_bytes_share",
                   "kda_step_roofline", "latent_decode_roofline",
                   "kda_time_share", "latent_attn_time_share")]
    assert len([m for m in entries
                if m["workloads"] == ["reasoning-offline"]]) == 6
    assert [m["name"] for m in entries
            if m["workloads"] != ["reasoning-offline"]] == [
        "latent_attn_time_share.docqa"]
    for s0, s1 in ((kimi._stats(counted=False),
                    kimi._stats(counted=False, steps=9)),
                   (before, before), ({}, {})):
        ctx = kimi._ctx(s0, s1)
        assert delta(ctx) is None
        for m in entries:
            assert cells.layer_reader(m["name"])(m["name"], ctx) is None


def test_the_tile_fill_share_is_the_chunk_classs_rows_over_its_tiles():
    """``expert_tile_fill_share.mixedlen`` (PR 42) on hand-made
    ``stats()`` pairs: the chunk class's assignments held over its
    ``tile_rows``, after less before; no number from a program that
    does not count its tiles (the parent) or that ran no chunk."""
    from benchmark import cells

    read = cells.layer_reader("expert_tile_fill_share.mixedlen")

    def stats(held, rows, step_rows=0.0):
        chunk = {"assignments_held": held}
        if rows is not None:
            chunk["tile_rows"] = rows
        return {"engine": {"moe": {"by_class": {
            "chunk": chunk,
            "single_token": {"assignments_held": 192.0,
                             "tile_rows": step_rows}}}}}

    ctx = {"stats0": stats(12288.0, 16384.0), "stats1": stats(
        12288.0 * 3, 16384.0 + 2 * 16000.0, step_rows=992.0)}
    assert read("expert_tile_fill_share.mixedlen", ctx) == pytest.approx(
        100.0 * 24576 / 32000)
    assert read("x", {"stats0": stats(0.0, None),
                      "stats1": stats(24576.0, None)}) is None
    assert read("x", {"stats0": stats(5.0, 16.0),
                      "stats1": stats(5.0, 16.0, step_rows=64.0)}) is None
    assert read("x", {"stats0": None, "stats1": {"engine": {}}}) is None
    [entry] = [m for m in cells.benchmark_spec()["per_layer"]
               if m["name"] == "expert_tile_fill_share.mixedlen"]
    assert entry["workloads"] == ["mixed-length-offline"]
    assert entry["layer"] == "expert layer"


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
