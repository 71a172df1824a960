"""The readers ``document-qa-offline`` adds, on hand-made ops and
``stats()``: the latent decode at 64 heads is costed by what the
mathematics needs (never by the leaf's 640 lanes or whole 512-token
blocks), its live tokens a call and the latent bytes a token come from
run deltas of the program's counters in a stack that has NO KDA layer
(where ``cache_counts.py`` reads nothing), the zero experts' share is
their assignments over all, the chunk form's ops are found by the score
block's shape, and a program that counts no such thing gives no number
and does not raise."""

import json

import pytest

from benchmark import cells

CELL = "document-qa-offline"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _ctx(stats0, stats1, trace=None):
    return {"cell": cells.Cell(CELL), "stats0": stats0, "stats1": stats1,
            "peaks": PEAKS, "trace": trace}


def _stats(tokens=0.0, fetched=0.0, steps=0, emitted=0, made=0.0, zero=None,
           counted=True):
    eng = {"steps": steps, "emitted_tokens": emitted}
    if counted:
        eng["latent"] = {"tokens_attended": tokens, "bytes_read": fetched,
                         "chunk_tokens": 0.0, "layer_calls": 8.0 * steps}
        eng["moe"] = {"assignments": made, "expert_layer_calls": 4.0 * steps}
        if zero is not None:
            eng["moe"]["zero_assignments"] = zero
    return {"engine": eng}


LATENT = ('%latent_decode.2 = (f32[24,64,512]{2,1,0}, '
          'bf16[24,8704,640]{2,1,0}) custom-call(s32[24]{0} %start, '
          's32[24]{0} %stop), custom_call_target="tpu_custom_call"')
SCORES = ('%fusion.41 = f32[1,64,256,512]{3,2,1,0} fusion(bf16[1,256,64,640]'
          '{3,2,1,0} %q, bf16[1,512,640]{2,1,0} %blk), kind=kOutput')
WEIGHTED = ('%fusion.44 = f32[64,256,512]{2,1,0} fusion(bf16[64,256,512]'
            '{2,1,0} %p, bf16[512,512]{1,0} %v), kind=kOutput')
LOOP = ('%while.320 = (s32[], f32[1,64,256]{2,1,0}, f32[1,64,256,512]'
        '{3,2,1,0}, bf16[1,8704,640]{2,1,0}) while((s32[], f32[1,64,256]) '
        '%tuple.9), condition=%cond, body=%body')
OTHER = '%fusion.2 = bf16[2048,6144]{1,0} fusion(), kind=kLoop'


class _Trace:
    """One chip's worth of nothing but the op events a reader asks for."""

    def __init__(self, events, busy):
        self.ops, self.devices, self._busy = {"tpu0": events}, ["tpu0"], busy

    def kernel_events(self, match):
        return [e for e in self.ops["tpu0"] if match(e[0])]

    def busy_s(self):
        return self._busy


def test_the_latent_decode_at_64_heads_is_costed_by_what_exists():
    cfg = cells.Cell(CELL).config
    dims = cells.architecture(cfg).dims_of(cfg)
    assert (dims["heads"], dims["latent"], dims["rope"], dims["mixers"]) \
        == (64, 512, 64, 8)
    lat = cells.roofline("latent_decode")
    assert lat.match(LATENT) and not lat.match(SCORES)
    flops, nbytes = lat.cost(LATENT, {"latent_dims": dims,
                                      "latent_tokens_per_call": 100000.0})
    assert nbytes == 100000.0 * 576 * 2 < 100000.0 * 640 * 2
    assert flops == 100000.0 * 64 * (576 + 512) * 2
    # 121 operations a byte, under the v5e's ridge of 240: bound by bytes
    assert flops / nbytes == pytest.approx(120.9, abs=0.1)
    assert nbytes / 819e9 > flops / 197e12


def test_longcat_tokens_and_bytes_a_token_are_run_deltas():
    from benchmark.layer_metrics.cache_counts import delta as kimi_delta
    from benchmark.layer_metrics.latent_counts import delta

    before = _stats(tokens=9e4, fetched=9e4 * 1280, steps=20, emitted=400)
    # 1,000 steps of 8 attention blocks, 20 live rows of ~4,000 tokens
    after = _stats(tokens=9e4 + 8 * 20 * 4000.0 * 1000, steps=1020,
                   fetched=(9e4 + 8 * 20 * 4300.0 * 1000) * 1280,
                   emitted=400 + 20000)
    ctx = _ctx(before, after)
    # the accepted readers ask for KDA counts beside the latent's
    assert kimi_delta(ctx) is None
    for name in ("latent_decode_roofline", "cache_bytes_per_token.reasoning"):
        assert cells.layer_reader(name)(name, ctx) is None
    got = delta(ctx)
    assert got["tokens_attended"] == 8 * 20 * 4000.0 * 1000
    assert got["steps"] == 1000 and got["emitted_tokens"] == 20000
    name = "latent_bytes_per_token.docqa"
    assert cells.layer_reader(name)(name, ctx) == pytest.approx(
        8 * 20 * 4300.0 * 1000 * 1280 / 20000 / 1e6)
    # the kernel's calls took 1 ms each: 80,000 live tokens a call
    ms = 1e6
    trace = _Trace([(LATENT, 0, 1 * ms), (OTHER, 1 * ms, 3 * ms),
                    (LATENT, 3 * ms, 4 * ms)], 4e-3)
    name = "mla_decode_roofline.docqa"
    roof = cells.layer_reader(name)(name, _ctx(before, after, trace))
    assert roof == pytest.approx(
        100.0 * (20 * 4000 * 1152 / 819e9) / 1e-3, rel=1e-6)
    # never over 100 when the leaf's 640 lanes and whole blocks are
    # what moved at the chip's full bandwidth
    stored = 20 * 4300 * 1280
    fastest = _Trace([(LATENT, 0, stored / 819e9 * 1e9)], 1e-3)
    roof = cells.layer_reader(name)(name, _ctx(before, after, fastest))
    assert roof == pytest.approx(100.0 * 4000 * 1152 / (4300 * 1280))
    assert roof < 100.0
    name = "latent_attn_time_share.docqa"
    assert cells.layer_reader(name)(name, _ctx(before, after, trace)) \
        == pytest.approx(100.0 * 2 / 4)


def test_the_zero_experts_share_is_their_assignments_over_all():
    name = "zero_expert_share.docqa"
    read = cells.layer_reader(name)
    before = _stats(made=1200.0, zero=400.0, steps=1)
    after = _stats(made=1200.0 + 288000.0, zero=400.0 + 96770.0, steps=1001)
    assert read(name, _ctx(before, after)) == pytest.approx(
        100.0 * 96770 / 288000)
    # a parent that counts no zero expert, a window without a call
    assert read(name, _ctx(_stats(made=5.0), _stats(made=9.0))) is None
    assert read(name, _ctx(before, before)) is None
    assert read(name, _ctx({}, {})) is None


def test_the_chunk_forms_ops_are_found_by_the_score_blocks_shape():
    name = "latent_chunk_time_share.docqa"
    read = cells.layer_reader(name)
    ms = 1e6
    # the loop spans the ops inside it: it is not counted again
    trace = _Trace([(LOOP, 0, 3 * ms), (SCORES, 0, 2 * ms),
                    (WEIGHTED, 2 * ms, 3 * ms), (OTHER, 3 * ms, 6 * ms),
                    (LATENT, 6 * ms, 8 * ms), (SCORES, 8 * ms, 10 * ms)],
                   10e-3)
    assert read(name, _ctx({}, {}, trace)) == pytest.approx(100.0 * 5 / 10)
    assert read(name, _ctx({}, {}, _Trace([(OTHER, 0, ms)], 1e-3))) is None
    assert read(name, _ctx({}, {}, None)) is None


def test_a_program_without_the_counts_gives_no_number_and_no_raise():
    from benchmark.layer_metrics.latent_counts import delta

    names = [m["name"] for m in cells.benchmark_spec()["per_layer"]
             if m["name"].split(".")[0] in (
                 "mla_decode_roofline", "latent_bytes_per_token",
                 "zero_expert_share")]
    assert len(names) == 3
    before = _stats(tokens=5.0, fetched=6400.0, steps=3, emitted=9, made=8.0,
                    zero=2.0)
    trace = _Trace([(LATENT, 0, 1e6)], 1e-3)
    for s0, s1 in ((_stats(counted=False), _stats(counted=False, steps=9)),
                   (before, before), ({}, {})):
        ctx = _ctx(s0, s1, trace)
        assert delta(ctx) is None
        for name in names:
            assert cells.layer_reader(name)(name, ctx) is None


def test_the_longcat_entries_name_the_cell_and_its_files():
    spec = cells.benchmark_spec()
    mine = [m for m in spec["per_layer"] if m.get("workloads") == [CELL]]
    assert len(mine) >= 17 and all(
        m["moves"] == "serve_tokens_per_s" for m in mine)
    assert all(m["name"].endswith(".docqa") for m in mine)
    assert all(cells.layer_reader(m["name"]) is not None for m in mine)
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert e2e["serve_tokens_per_s"]["workloads"][-1] == CELL
    cell = cells.Cell(CELL)
    assert cell.config["reference"] == "longcat_flash" and cell.chips == 1
    assert spec["workloads"][-1]["name"] == CELL
    assert [c["reduced"] for c in spec["configs"]
            if c["name"] == "longcat-flash-chat-serve"] == [
        ["num_layers", "n_routed_experts", "vocab_size"]]
    with open(cells.ROOT
              / "benchmark/configs/longcat-flash-chat-serve.json") as f:
        cfg = json.load(f)
    assert (cfg["num_layers"], cfg["n_routed_experts"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 16, 16, 16384)
    assert sorted(cfg["reduced"]) == sorted(
        ["num_layers", "n_routed_experts", "vocab_size"])
    # every width as published
    assert (cfg["hidden_size"], cfg["ffn_hidden_size"],
            cfg["expert_ffn_hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["zero_expert_num"], cfg["moe_topk"],
            cfg["routed_scaling_factor"], cfg["rope_theta"]) == (
        6144, 12288, 2048, 64, 128, 64, 128, 1536, 512, 256, 12, 6, 10000000)
    model = cfg["model"]
    assert model["experts"] + model["zero_experts"] == 768
    assert (model["hidden"], model["mlp_dim"], model["expert_width"],
            model["latent_dims"], model["latent_q_rank"]) == (
        6144, 12288, 2048, [128, 64, 128, 512], 1536)
    slots = cfg["service"]["batch_sizes"][-1]
    assert cell.traffic["clients"] == slots * 5 // 4
    assert cell.traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 4096, "sigma": 0.7, "min": 512,
        "max": 8192}
    assert cell.traffic["output_tokens"] == {
        "dist": "uniform", "min": 128, "max": 256}
