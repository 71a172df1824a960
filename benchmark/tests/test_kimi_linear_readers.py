"""The readers ``reasoning-offline`` adds, on hand-made ops and
``stats()``: the KDA step and the latent decode are costed by what the
mathematics needs (never by a padded leaf's or a whole block's shapes),
their live rows and tokens a call and the cache bytes a token come from
run deltas of the program's counters, the time shares match the kernels
by name, and a program that counts no such thing gives no number and
does not raise."""

import json

import pytest

from benchmark import cells

CELL = "reasoning-offline"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _ctx(stats0, stats1, trace=None):
    return {"cell": cells.Cell(CELL), "stats0": stats0, "stats1": stats1,
            "peaks": PEAKS, "trace": trace}


def _stats(rows=0.0, state=0.0, tokens=0.0, fetched=0.0, steps=0, emitted=0,
           counted=True):
    eng = {"steps": steps, "emitted_tokens": emitted}
    if counted:
        eng["kda"] = {"state_rows": rows, "state_bytes": state,
                      "chunk_tokens": 0.0, "layer_calls": 4.0 * steps}
        eng["latent"] = {"tokens_attended": tokens, "bytes_read": fetched,
                         "chunk_tokens": 0.0, "layer_calls": 1.0 * steps}
    return {"engine": eng}


KDA = ('%kda_step.3 = (f32[112,32,8,128]{3,2,1,0}, '
       'f32[112,32,128,128]{3,2,1,0}) custom-call(s32[112]{0} %rows, '
       's32[1]{0} %n), custom_call_target="tpu_custom_call"')
LATENT = ('%latent_decode.2 = (f32[112,32,512]{2,1,0}, '
          'bf16[112,10240,640]{2,1,0}) custom-call(s32[112]{0} %start, '
          's32[112]{0} %stop), custom_call_target="tpu_custom_call"')


class _Trace:
    """One chip's worth of nothing but the op events a reader asks for."""

    def __init__(self, events, busy):
        self.ops, self.devices, self._busy = {"tpu0": events}, ["tpu0"], busy

    def kernel_events(self, match):
        return [e for e in self.ops["tpu0"] if match(e[0])]

    def busy_s(self):
        return self._busy


def test_the_kimi_kernels_are_costed_by_the_least_the_mathematics_needs():
    cfg = cells.Cell(CELL).config
    dims = cells.architecture(cfg).dims_of(cfg)
    assert dims["attn"] == ["kda", "kda", "kda", "latent", "kda"]
    kda = cells.roofline("kda_step")
    assert kda.match(KDA) and not kda.match(LATENT)
    flops, nbytes = kda.cost(KDA, {"kda_dims": dims,
                                   "kda_rows_per_call": 90.0})
    a_head = 128 * 128 * 4 * 2 + 3 * 128 * 2 + 128 * 4 + 4 + 128 * 4
    assert nbytes == 90.0 * 32 * a_head
    assert flops == 90.0 * 32 * 7 * 128 * 128
    # bound by bytes: 90 rows take 0.47 ms at 819 GB/s, 1.7 us of products
    assert nbytes / 819e9 > 100 * flops / 197e12
    lat = cells.roofline("latent_decode")
    assert lat.match(LATENT) and not lat.match(KDA)
    flops, nbytes = lat.cost(LATENT, {"latent_dims": dims,
                                      "latent_tokens_per_call": 300000.0})
    # the 576 numbers a token that exist, not the leaf's 640 lanes nor
    # whole 512-token blocks
    assert nbytes == 300000.0 * 576 * 2 < 300000.0 * 640 * 2
    assert flops == 300000.0 * 32 * (576 + 512) * 2
    # 60 operations a byte, under the v5e's ridge of 240: bound by bytes
    assert flops / nbytes == pytest.approx(60.4, abs=0.1)
    assert nbytes / 819e9 > flops / 197e12


def test_kimi_rows_tokens_and_bytes_a_token_are_run_deltas():
    from benchmark.layer_metrics.cache_counts import delta

    a_row = 32 * 128 * 128 * 4 * 2
    before = _stats(rows=400.0, state=400.0 * a_row, tokens=9e4,
                    fetched=9e4 * 1280, steps=20, emitted=2100)
    after = _stats(rows=400.0 + 95.0 * 4 * 1000, steps=1020,
                   state=(400.0 + 95.0 * 4 * 1000) * a_row,
                   tokens=9e4 + 95.0 * 3000 * 1000,
                   fetched=(9e4 + 95.0 * 3300 * 1000) * 1280,
                   emitted=2100 + 95000)
    ctx = _ctx(before, after)
    got = delta(ctx)
    assert got["kda"]["state_rows"] == 95.0 * 4 * 1000
    assert got["latent"]["tokens_attended"] == 95.0 * 3000 * 1000
    assert got["steps"] == 1000
    state, latent = 95.0 * 4 * 1000 * a_row, 95.0 * 3300 * 1000 * 1280
    name = "cache_bytes_per_token.reasoning"
    assert cells.layer_reader(name)(name, ctx) == pytest.approx(
        (state + latent) / 95000 / 1e6)
    name = "latent_bytes_share.reasoning"
    assert cells.layer_reader(name)(name, ctx) == pytest.approx(
        100.0 * latent / (state + latent))
    # a parent without the layers, a model without them, a window
    # without a step: nothing to read, and no raise
    names = [m["name"] for m in cells.benchmark_spec()["per_layer"]
             if m["name"].split(".")[0] in (
                 "cache_bytes_per_token", "latent_bytes_share",
                 "kda_step_roofline", "latent_decode_roofline",
                 "kda_time_share", "latent_attn_time_share")]
    assert len(names) == 6
    for s0, s1 in ((_stats(counted=False), _stats(counted=False, steps=9)),
                   (before, before), ({}, {})):
        ctx = _ctx(s0, s1)
        assert delta(ctx) is None
        for name in names:
            assert cells.layer_reader(name)(name, ctx) is None


def test_the_kimi_time_shares_and_rooflines_read_the_kernels_by_name():
    other = "%fusion.2 = bf16[112,2304]{1,0} fusion(), kind=kLoop"
    ms = 1e6
    trace = _Trace([(KDA, 0, 2 * ms), (LATENT, 2 * ms, 3 * ms),
                    (other, 3 * ms, 6 * ms), (KDA, 6 * ms, 8 * ms),
                    (KDA, 8 * ms, 10 * ms), (KDA, 10 * ms, 12 * ms)], 16e-3)
    a_row = 32 * 128 * 128 * 4 * 2
    ctx = _ctx(_stats(), _stats(
        rows=100.0 * 4 * 50, state=100.0 * 4 * 50 * a_row,
        tokens=250000.0 * 50, fetched=290000.0 * 50 * 1280, steps=50,
        emitted=5000), trace)
    for name, want in (("kda_time_share.reasoning", 100.0 * 8 / 16),
                       ("latent_attn_time_share.reasoning", 100.0 * 1 / 16)):
        assert cells.layer_reader(name)(name, ctx) == pytest.approx(want)
    roof = cells.layer_reader("kda_step_roofline")("kda_step_roofline", ctx)
    a_head = 128 * 128 * 4 * 2 + 3 * 128 * 2 + 128 * 4 + 4 + 128 * 4
    assert roof == pytest.approx(
        100.0 * (100 * 32 * a_head / 819e9) / 2e-3, rel=1e-6)
    roof = cells.layer_reader("latent_decode_roofline")(
        "latent_decode_roofline", ctx)
    assert roof == pytest.approx(
        100.0 * (250000 * 1152 / 819e9) / 1e-3, rel=1e-6)
    assert roof < 100.0


def test_the_kimi_entries_name_the_cell_and_its_files():
    spec = cells.benchmark_spec()
    mine = [m for m in spec["per_layer"] if m.get("workloads") == [CELL]]
    assert len(mine) >= 17 and all(
        m["moves"] == "serve_tokens_per_s" for m in mine)
    assert all(cells.layer_reader(m["name"]) is not None for m in mine)
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    cell = cells.Cell(CELL)
    assert cell.config["reference"] == "kimi_linear" and cell.chips == 1
    assert [c["reduced"] for c in spec["configs"]
            if c["name"] == "kimi-linear-48b-a3b-serve"] == [
        ["num_hidden_layers", "num_experts", "vocab_size"]]
    with open(cells.ROOT
              / "benchmark/configs/kimi-linear-48b-a3b-serve.json") as f:
        cfg = json.load(f)
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 32, 20480)
    # every width as published
    assert (cfg["hidden_size"], cfg["moe_intermediate_size"],
            cfg["kv_lora_rank"], cfg["num_experts_per_token"],
            cfg["linear_attn_config"]["head_dim"]) == (2304, 1024, 512, 8, 128)
    slots = cfg["service"]["batch_sizes"][-1]
    assert cell.traffic["clients"] == slots * 5 // 4
