"""The arithmetic the numbers rest on: percentiles, lateness, the traffic
generator, the trace reduction, the rooflines, the files of each cell."""

import json
import re

import numpy as np
import pytest

from benchmark import cells, stats, traffic, xplane

CAPTURE = cells.ROOT / "tests" / "data" / "tpu_v5e_capture.xplane.pb"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("q,want", [(50, 3.0), (90, 5.0), (100, 5.0), (1, 1.0)])
def test_percentile_is_nearest_rank(q, want):
    assert stats.percentile([5.0, 1.0, 3.0, 2.0, 4.0], q) == want


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 90)


def test_lateness_counts_only_late_sends():
    late = stats.lateness([0.0, 1.0, 2.0], [0.001, 0.9, 2.1])
    assert late["n"] == 3 and late["max_ms"] == pytest.approx(100.0)
    assert late["p50_ms"] == pytest.approx(1.0)


def test_worst_leaf_gap_uses_the_median_leaf_for_tiny_norms():
    # the tiny leaf differs by 100% of itself but 0.1% of the median leaf
    assert stats.worst_leaf_gap([1.0, 2.0, 2e-3], [1.0, 2.0, 1e-3]) == (
        pytest.approx(1e-3)
    )
    assert stats.worst_leaf_gap([1.1, 2.0, 3.0], [1.0, 2.0, 3.0]) == (
        pytest.approx(0.05)
    )


MIX = {
    "schedule_seed": 24,
    "prompt_tokens": {"dist": "lognormal", "median": 384, "sigma": 0.8,
                      "min": 32, "max": 2048},
    "output_tokens": {"dist": "uniform", "min": 8, "max": 256},
}


def test_traffic_is_the_same_trace_for_every_seed():
    a = traffic.requests(MIX, 64, 1000, seed=1)
    b = traffic.requests(MIX, 64, 1000, seed=2**31 + 5)
    assert [len(r["ids"]) for r in a] == [len(r["ids"]) for r in b]
    assert [r["n_new"] for r in a] == [r["n_new"] for r in b]
    assert a[0]["ids"] != b[0]["ids"]  # the seed draws the tokens
    assert a == traffic.requests(MIX, 64, 1000, seed=1)
    assert all(32 <= len(r["ids"]) <= 2048 and 8 <= r["n_new"] <= 256
               for r in a)


def test_schedule_seed_orders_the_same_set():
    other = dict(MIX, schedule_seed=25)
    a = [len(r["ids"]) for r in traffic.requests(MIX, 64, 1000, 1)]
    b = [len(r["ids"]) for r in traffic.requests(other, 64, 1000, 1)]
    assert a != b and sorted(a) == sorted(b)


def test_arrivals_keep_the_rate_and_the_exponential_marginal():
    due = traffic.arrivals(4.0, 50.0, 24)
    assert np.all(np.diff(due) > 0) and due[-1] < 50.0
    assert len(due) == pytest.approx(200, abs=4)
    gaps = np.diff(due)
    assert gaps.mean() == pytest.approx(0.25, rel=0.05)
    assert gaps.std() == pytest.approx(0.25, rel=0.15)  # exponential


def test_blocked_order_spreads_every_stratum_over_every_block():
    rng = np.random.default_rng(0)
    out = traffic.blocked_order(np.arange(160), rng)
    assert sorted(out) == list(range(160))
    for b in range(10):
        strata = sorted(v // 10 for v in out[b * 16:(b + 1) * 16])
        assert strata == list(range(16))


def test_token_rows_differ_and_repeat_under_the_seed():
    a = traffic.token_rows(4, 32, 1000, 7)
    assert (a == traffic.token_rows(4, 32, 1000, 7)).all()
    assert len({bytes(r) for r in a}) == 4
    assert not (a == traffic.token_rows(4, 32, 1000, 8)).all()


@pytest.fixture(scope="module")
def capture():
    return xplane.Trace(str(CAPTURE))


def test_trace_reads_the_v5e_capture(capture):
    assert capture.devices == ["/device:TPU:0"]
    assert capture.busy_s() == pytest.approx(1.874491e-3, rel=1e-6)
    totals = capture.op_totals()
    assert totals["flash_fwd_kernel_tri"] == (pytest.approx(1.362981e-3), 1)
    assert totals["decode_attention"][1] == 1
    assert len(capture.module_spans(r"^jit_")) == 3
    assert capture.window_s(2.0) == 2.0  # no bench.slice span in it


def test_breakdown_lists_ops_and_gaps(capture):
    bd = capture.breakdown()
    assert bd["device_ops"][0][0] == "flash_fwd_kernel_tri"
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["idle_gaps"][0][1] > 0


def test_union_and_gaps():
    spans = [("a", 0.0, 10.0), ("b", 5.0, 12.0), ("c", 20.0, 30.0)]
    assert xplane.union_ns(spans) == 22.0
    assert xplane.gaps(spans) == [(12.0, 20.0)]


@pytest.mark.parametrize("kernel,prefix,share", [
    ("int8_matmul", "%quant_matmul", (0.5, 100.0)),
    ("kv8_decode_attn", "%decode_attention", (0.5, 100.0)),
    ("flash_fwd_bwd", "%flash", (10.0, 100.0)),
])
def test_rooflines_cost_the_captured_kernels(capture, kernel, prefix, share):
    from benchmark.device import PEAKS

    mod = cells.roofline(kernel)
    events = capture.kernel_events(mod.match)
    assert len(events) == 1 and events[0][0].startswith(prefix)
    # the capture's decode call had 8 slots and a 2304-token buffer; how
    # much of it was live the capture does not say: take half
    ctx = {"kv_live_tokens": 8 * 1152}
    flops, nbytes = mod.cost(events[0][0], ctx)
    assert flops > 0 and nbytes > 0
    got = xplane.roofline_share(capture, mod, PEAKS["TPU v5 lite"], ctx)
    assert share[0] < got <= share[1]


def test_int8_matmul_cost_is_from_the_ops_own_shapes(capture):
    mod = cells.roofline("int8_matmul")
    op = capture.kernel_events(mod.match)[0][0]
    flops, nbytes = mod.cost(op, {})
    assert flops == 2.0 * 8 * 2048 * 2048
    assert nbytes == 2048 * 2048 + 2 * (8 * 2048 * 2) + 8 * 2048 * 4


def test_flash_cost_counts_causal_forward(capture):
    mod = cells.roofline("flash_fwd_bwd")
    op = capture.kernel_events(mod.match)[0][0]
    flops, _ = mod.cost(op, {})
    assert flops == 2.0 * 2.0 * 2 * 16 * 4096 * 4096 * 128 * 0.5


def test_benchmark_json_names_units_and_files():
    spec = cells.benchmark_spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
    cell_names = {w["name"] for w in spec["workloads"]}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cell_names
        assert cells.layer_reader(m["name"]) is not None, m["name"]
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        for rehearsal in (False, True):
            cell = cells.Cell(w["name"], rehearsal=rehearsal)
            assert cell.traffic["kind"] in ("open_loop", "closed_loop",
                                            "train_steps")
            cells.kind_runner(cell.traffic["kind"])
            assert len(cell.end_to_end()) >= 2 and cell.per_layer()
            assert set(cell.traffic["limits"])
    for c in spec["configs"]:
        cfg = json.loads((cells.ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"] and len(c["source"]) <= 200
        path = cells.ROOT / c["file"]
        tiny = json.loads((path.parent / "_rehearsal" / path.name).read_text())
        for f in (cfg, tiny):   # both name an architecture and a precision
            arch = cells.architecture(f)
            d = arch.dims_of(f)
            assert len(arch.layer_kinds(d)) == d["layers"]
            assert set(f["rounding"]) <= {"weights", "kv", "operands"}
        assert tiny["rounding"] == cfg["rounding"]


def test_decode_step_ms_counts_plain_dispatches_only():
    import re

    class FakeTrace:
        spans = [("jit_dispatch(1)", 0.0, 52e6), ("jit_fused(2)", 60e6, 120e6),
                 ("jit_dispatch(1)", 130e6, 182e6), ("jit_insert", 190e6, 191e6)]

        def module_spans(self, pattern):
            return [s for s in self.spans if re.search(pattern, s[0])]

    cell = cells.Cell("chat-steady", rehearsal=True)
    read = cells.layer_reader("decode_step_ms.steady")
    assert read("decode_step_ms.steady", {"trace": FakeTrace(), "cell": cell}
                ) == pytest.approx(13.0)  # 104 ms over 2 dispatches of K=4
    FakeTrace.spans = FakeTrace.spans[1:2]
    assert read("decode_step_ms.steady",
                {"trace": FakeTrace(), "cell": cell}) is None


@pytest.mark.parametrize("mix, seconds, want", [
    (None, 50.0, 4.0),                     # no word from the mix: a fifth, at most 4 s
    (None, 10.0, 2.0),
    ({"trace_slice_s": 1.0}, 50.0, 1.0),   # the serve mixes: 1 s
    ({"trace_slice_s": 1.0}, 3.0, 0.6),    # never more than a fifth of the window
])
def test_traced_slice_length_is_the_mixs(mix, seconds, want):
    from benchmark.harness import TracedSlice

    sl = TracedSlice.steady(True, seconds, mix)
    assert sl.length_s == pytest.approx(want)
    assert sl.start_s == pytest.approx(0.4 * seconds)


def test_serve_mixes_capture_one_second():
    # 4 s of a serve cell's 690,000 ops a second took the profiler minutes
    # to stop and the run past its 360 s (PR 24's refusal): the files say 1 s
    for name in ("chat-steady", "batch-offline", "long-prefill"):
        assert cells.Cell(name).traffic["trace_slice_s"] == 1.0


@pytest.mark.parametrize("what, stated, control", [
    ("weights", "int8", "int4"), ("weights", "bfloat16", "int8"),
    ("kv", "int8", "int4"), ("kv", "bfloat16", "int8"),
    ("operands", "bfloat16", "fp8"),
])
def test_a_control_is_the_nearest_precision_below_the_stated_one(
        what, stated, control):
    from benchmark.reference import quant

    assert quant.stated({"rounding": {what: stated}}, what) == stated
    assert quant.below(what, stated) == control
    assert quant.stated({"rounding": {}}, what) is None
    with pytest.raises(SystemExit):
        quant.stated({"rounding": {what: "int3"}}, what)
    with pytest.raises(SystemExit):
        quant.stated({}, what)


def test_rounding_to_a_named_precision():
    import jax.numpy as jnp

    from benchmark.reference import quant

    x = jnp.linspace(-1.0, 1.0, 64).reshape(2, 32)
    assert quant.weight_qmax("int8") == 127 and quant.weight_qmax("int4") == 7
    assert quant.weight_qmax("bfloat16") is None   # drawn in it already
    for name, levels in (("int8", 255), ("int4", 15)):
        q = quant.kv_round(name)(x)
        assert q.shape == x.shape
        assert all(len(set(map(float, row))) <= levels for row in q)
    coarse = float(jnp.abs(quant.kv_round("int4")(x) - x).max())
    fine = float(jnp.abs(quant.kv_round("int8")(x) - x).max())
    assert fine < coarse / 8
    assert quant.operand_round("fp8") is quant.fp8
