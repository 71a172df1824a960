"""The readers ``continuation-offline`` adds, on hand-made ops and
``stats()``: the retention step is costed by what the mathematics needs
(never by a padded state's shapes), its live rows a call and the bytes a
token come from run deltas of the program's counters, the layer's time
share matches the kernel by name and its other ops by scope, and a
program that counts no such thing gives no number and does not raise."""

import json

import pytest

from benchmark import cells


def _cell_ctx(stats0, stats1):
    cell = cells.Cell("continuation-offline")
    return {"cell": cell, "stats0": stats0, "stats1": stats1,
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


def _stats(rows=0.0, nbytes=0.0, steps=0, emitted=0, ret=True):
    eng = {"steps": steps, "emitted_tokens": emitted}
    if ret:
        eng["retention"] = {"state_rows": rows, "state_bytes": nbytes,
                            "chunk_tokens": 0.0, "layer_calls": 5.0 * steps}
    return {"engine": eng}


STEP = ('%retention_step.3 = (f32[20,8,8,128]{3,2,1,0}, '
        'f32[20,8,8320,128]{3,2,1,0}, f32[20,8,65,128]{3,2,1,0}) '
        'custom-call(s32[20]{0} %rows, s32[1]{0} %n), '
        'custom_call_target="tpu_custom_call"')


def test_the_step_is_costed_by_the_least_state_the_mathematics_needs():
    cfg = cells.Cell("continuation-offline").config
    dims = cells.architecture(cfg).dims_of(cfg)
    assert dims["expanded"] == 128 * 129 // 2 == 8256
    mod = cells.roofline("retention_step")
    assert mod.match(STEP) and not mod.match("%fusion.7 = f32[8]{0} fusion()")
    flops, nbytes = mod.cost(STEP, {"retention_dims": dims,
                                    "retention_rows_per_call": 18.0})
    state = 8 * (8256 * 128 + 8256) * 4 * 2
    assert nbytes == 18.0 * (state + 2 * 56 * 128 + 4 * (8 + 40 * 128))
    assert flops == 18.0 * (8 * 2 + 40 * 2) * 8256 * 128
    # the op's own (padded) shapes are not what is counted
    assert state < 8 * 8320 * 129 * 4 * 2
    # bound by bytes: 18 rows take 1.5 ms at 819 GB/s, 9 us of products
    assert nbytes / 819e9 > 100 * flops / 197e12


def test_rows_a_call_and_bytes_a_token_are_run_deltas():
    from benchmark.layer_metrics.retention_counts import delta

    a_row = 8 * 8320 * 129 * 4 * 2          # what the program's walk moves
    before = _stats(rows=1000.0, nbytes=1000.0 * a_row, steps=20, emitted=210)
    after = _stats(rows=1000.0 + 18.5 * 5 * 400, steps=420,
                   nbytes=(1000.0 + 18.5 * 5 * 400) * a_row,
                   emitted=210 + 7450)
    ctx = _cell_ctx(before, after)
    got = delta(ctx)
    assert got["state_rows"] == 18.5 * 5 * 400 and got["steps"] == 400
    read = cells.layer_reader("state_bytes_per_token.continuation")
    assert read("state_bytes_per_token.continuation", ctx) == pytest.approx(
        18.5 * 5 * 400 * a_row / 7450 / 1e6)
    # a parent without the layer, a model without it, a window without a
    # step: nothing to read, and no raise
    for s0, s1 in ((_stats(ret=False), _stats(ret=False, steps=9)),
                   (before, before), ({}, {})):
        ctx = _cell_ctx(s0, s1)
        assert delta(ctx) is None
        assert read("state_bytes_per_token.continuation", ctx) is None
        ctx["trace"] = None
        for name in ("retention_step_roofline",
                     "retention_time_share.continuation"):
            assert cells.layer_reader(name)(name, ctx) is None


class _Trace:
    """Two chips' worth of nothing but the op events a reader asks for."""

    def __init__(self, events, busy):
        self.ops, self.devices, self._busy = {"tpu0": events}, ["tpu0"], busy

    def kernel_events(self, match):
        return [e for e in self.ops["tpu0"] if match(e[0])]

    def busy_s(self):
        return self._busy


def test_the_time_share_and_the_roofline_read_the_kernel_by_name():
    chunk_op = ('%fusion.9 = f32[1,1024,8,5,128]{4,3,2,1,0} fusion(), '
                'metadata={op_name="jit(fused)/layer_0/attn/retention.chunk/'
                'dot_general"}')
    other = "%fusion.2 = bf16[20,5120]{1,0} fusion(), kind=kLoop"
    ms = 1e6
    trace = _Trace([(STEP, 0, 2 * ms), (chunk_op, 2 * ms, 3 * ms),
                    (other, 3 * ms, 6 * ms), (STEP, 6 * ms, 8 * ms)], 8e-3)
    a_row = 8 * 8320 * 129 * 4 * 2
    ctx = _cell_ctx(_stats(), _stats(rows=20.0 * 5 * 100, steps=100,
                                     nbytes=20.0 * 5 * 100 * a_row,
                                     emitted=2000))
    ctx["trace"] = trace
    share = cells.layer_reader("retention_time_share.continuation")
    assert share("retention_time_share.continuation", ctx) == pytest.approx(
        100.0 * 5 / 8)
    roof = cells.layer_reader("retention_step_roofline")
    least = 20 * (8 * (8256 * 129) * 8 + 2 * 56 * 128 + 4 * (8 + 5120)) / 819e9
    assert roof("retention_step_roofline", ctx) == pytest.approx(
        100.0 * least / 2e-3, rel=1e-6)
    assert roof("retention_step_roofline", ctx) < 100.0


def test_the_entries_name_the_cell_and_its_files():
    spec = cells.benchmark_spec()
    mine = [m for m in spec["per_layer"]
            if m.get("workloads") == ["continuation-offline"]]
    assert len(mine) == 8 and all(
        m["moves"] == "serve_tokens_per_s" for m in mine)
    assert all(cells.layer_reader(m["name"]) is not None for m in mine)
    cell = cells.Cell("continuation-offline")
    assert cell.config["reference"] == "brumby" and cell.chips == 1
    assert [c["reduced"] for c in spec["configs"]
            if c["name"] == "brumby-14b-serve"] == [["num_hidden_layers"]]
    with open(cells.ROOT / "benchmark/configs/brumby-14b-serve.json") as f:
        assert json.load(f)["num_hidden_layers"] == 5
