"""The readers ``synthesis-offline`` adds, on hand-made ops and
``stats()``: the decode attention at a head of 64 is costed at the
PUBLISHED width (never by the op's 128 padded lanes), the bytes of cache
read a token come from run deltas of the program's counters and from
the cache leaves' own shapes (pad lanes included: that is where they
show), and a program that counts no conv layer gives no number and does
not raise."""

import json

import pytest

from benchmark import cells

CELL = "synthesis-offline"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("cache_bytes_read_per_token.synthesis", "conv_state_share.synthesis",
       "gqa64_decode_attn_roofline", "expert_step_tile_fill_share.synthesis")


def _stats(attended=0.0, rows=0.0, tails=0.0, emitted=0, counted=True,
           steps=0):
    eng = {"steps": steps, "emitted_tokens": emitted,
           "attention": {"kv_tokens_attended": attended,
                         "kv_tokens_live": attended}}
    if counted:
        eng["conv"] = {"state_rows": rows, "state_bytes": tails,
                       "chunk_tokens": 0.0, "layer_calls": 4.0 * steps}
    return {"engine": eng}


DECODE = ('%decode_attention.7 = (bf16[224,32,128]{2,1,0}, '
          's8[224,8,3840,128]{3,2,1,0}, bf16[224,8,1,3840]{3,2,1,0}, '
          's8[224,8,3840,128]{3,2,1,0}, bf16[224,8,1,3840]{3,2,1,0}) '
          'custom-call(bf16[224,32,128]{2,1,0} %q, '
          's8[224,8,3840,128]{3,2,1,0} %k), '
          'custom_call_target="tpu_custom_call"')
CHUNK = ('%decode_attention_chunk.2 = bf16[1,2048,32,128]{3,2,1,0} '
         'custom-call(bf16[1,2048,32,128]{3,2,1,0} %q), '
         'custom_call_target="tpu_custom_call"')


class _Stream:
    def __init__(self, t_first, t_last, n):
        self.t_first, self.t_last, self.n = t_first, t_last, n


class _Req:
    def __init__(self, n_prompt, t_first, t_last, n):
        self.ids, self.stream = [1] * n_prompt, _Stream(t_first, t_last, n)


class _Trace:
    """One chip's worth of nothing but the op events a reader asks for."""

    def __init__(self, events):
        self.ops, self.devices = {"tpu0": events}, ["tpu0"]

    def kernel_events(self, match):
        return [e for e in self.ops["tpu0"] if match(e[0])]


def test_the_decode_attention_is_costed_at_the_published_head_width():
    cfg = cells.Cell(CELL).config
    dims = cells.architecture(cfg).dims_of(cfg)
    assert (dims["heads"], dims["kv_heads"], dims["head_dim"]) == (32, 8, 64)
    roof = cells.roofline("gqa64_decode_attn")
    assert roof.match(DECODE) and not roof.match(CHUNK)
    flops, nbytes = roof.cost(DECODE, {"gqa_dims": dims,
                                       "kv_live_tokens": 400000.0})
    # 8 KV heads x (64 B of K + 64 B of V + two bfloat16 scales) a
    # token: half of what the op's 128 lanes hold
    assert nbytes == 400000.0 * 8 * (2 * 64 + 4)
    assert nbytes < 400000.0 * 8 * (2 * 128 + 4) * 0.52
    assert flops == 400000.0 * 32 * 64 * 4
    # 7.8 operations a byte, far under the v5e's ridge: bound by bytes
    assert nbytes / 819e9 > 10 * flops / 197e12
    # the accepted reader would read the op's own 128 lanes
    stored = cells.roofline("kv8_decode_attn").cost(
        DECODE, {"kv_live_tokens": 400000.0})[1]
    assert stored == 400000.0 * 8 * (2 * 128 + 4)


def test_the_roofline_reads_live_tokens_from_the_requests_clocks():
    # 200 requests of 500 prompt tokens, each streaming 1,001 tokens
    # over 10 s that cover the slice: live tokens in [4, 5] are
    # 200 x (500 + 1 + 100 x 4.5)
    reqs = [_Req(500, 0.0, 10.0, 1001) for _ in range(200)]
    live = 200 * (501 + 450.0)
    ms = 1e6
    trace = _Trace([(DECODE, 0, 1 * ms), (CHUNK, 1 * ms, 3 * ms),
                    (DECODE, 3 * ms, 4 * ms)])
    ctx = {"cell": cells.Cell(CELL), "trace": trace, "slice": (4.0, 5.0),
           "peaks": PEAKS, "window": {"reqs": reqs}}
    name = "gqa64_decode_attn_roofline"
    got = cells.layer_reader(name)(name, ctx)
    assert got == pytest.approx(
        100.0 * (live * 8 * 132 / 819e9) / 1e-3, rel=1e-6)
    assert got < 100.0
    for lacking in ({**ctx, "trace": None}, {**ctx, "peaks": None},
                    {**ctx, "slice": (None, None)},
                    {**ctx, "trace": _Trace([(CHUNK, 0, ms)])}):
        assert cells.layer_reader(name)(name, lacking) is None


def test_bytes_read_a_token_are_run_deltas_at_the_width_stored():
    from benchmark.layer_metrics.cache_bytes_read_per_token import (
        stored_bytes_a_token_a_layer,
    )

    cell = cells.Cell(CELL)
    # a head of 64 in 128 lanes: 8 heads x (128 + 128) B + two scales
    a_token = stored_bytes_a_token_a_layer(cell.config["model"])
    assert a_token == 8 * (2 * 128 + 4)
    k = cell.config["service"]["steps_per_dispatch"]
    a_tail = 2 * 2 * 2048 * 2            # read and written, bfloat16
    before = _stats(attended=3e5, rows=900.0, tails=900.0 * a_tail,
                    emitted=800, steps=4)
    after = _stats(attended=3e5 + 200.0 * 1500 * 250,
                   rows=900.0 + 200.0 * 4 * 1000,
                   tails=(900.0 + 200.0 * 4 * 1000) * a_tail,
                   emitted=800 + 200000, steps=1004)
    ctx = {"cell": cell, "stats0": before, "stats1": after}
    kv = 200.0 * 1500 * 250 * k * a_token
    tails = 200.0 * 4 * 1000 * a_tail
    name = "cache_bytes_read_per_token.synthesis"
    assert cells.layer_reader(name)(name, ctx) == pytest.approx(
        (kv + tails) / 200000 / 1e6)
    name = "conv_state_share.synthesis"
    assert cells.layer_reader(name)(name, ctx) == pytest.approx(
        100.0 * tails / (kv + tails))
    # a parent without the kind, a model without it, a window without a
    # token: nothing to read, and no raise
    for s0, s1 in ((_stats(counted=False), _stats(counted=False, steps=9)),
                   (before, before), ({}, {})):
        lacking = {"cell": cell, "stats0": s0, "stats1": s1}
        for name in NEW[:2]:
            assert cells.layer_reader(name)(name, lacking) is None
    # and a model that keeps no int8 keys and values has no such bytes
    assert stored_bytes_a_token_a_layer(
        {**cell.config["model"], "kv_quant": False}) is None


def test_the_step_class_tile_fill_is_the_single_token_calls_own():
    def moe(chunk, step):
        return {"engine": {"moe": {"by_class": {
            "chunk": dict(zip(("assignments_held", "tile_rows"), chunk)),
            "single_token": dict(zip(("assignments_held", "tile_rows"),
                                     step)),
        }}}}

    name = "expert_step_tile_fill_share.synthesis"
    read = cells.layer_reader(name)
    ctx = {"stats0": moe((8192.0, 16384.0), (896.0, 1280.0)),
           "stats1": moe((5 * 8192.0, 5 * 16384.0),
                         (896.0 + 100 * 896.0, 1280.0 + 100 * 1216.0))}
    # the run's delta of the single-token class alone: 896 assignments a
    # call in 76 tiles of 16 rows; the chunk class's 50% is not in it
    assert read(name, ctx) == pytest.approx(100.0 * 896 / 1216)
    # a program that counts no tiles by class, one that ran no
    # single-token call, and a parent without experts: nothing, no raise
    no_tiles = {"engine": {"moe": {"by_class": {
        "single_token": {"assignments_held": 896.0}}}}}
    for s0, s1 in ((no_tiles, no_tiles), (ctx["stats0"], ctx["stats0"]),
                   ({}, {}), (None, None)):
        assert read(name, {"stats0": s0, "stats1": s1}) is None


def test_the_lfm2_entries_name_the_cell_and_its_files():
    spec = cells.benchmark_spec()
    mine = [m for m in spec["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in mine} >= set(NEW)
    assert all(m["moves"] == "serve_tokens_per_s" for m in mine)
    assert all(cells.layer_reader(m["name"]) is not None for m in mine)
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    cell = cells.Cell(CELL)
    assert cell.config["reference"] == "lfm2_moe" and cell.chips == 1
    assert [c["reduced"] for c in spec["configs"]
            if c["name"] == "lfm2-24b-a2b-serve"] == [["num_hidden_layers"]]
    with open(cells.ROOT / "benchmark/configs/lfm2-24b-a2b-serve.json") as f:
        cfg = json.load(f)
    # every width as published; the depth alone is cut
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["conv_L_cache"],
            cfg["vocab_size"], cfg["rope_parameters"]["rope_theta"]) == (
        2048, 11776, 1536, 64, 4, 32, 8, 3, 65536, 1000000)
    assert cfg["num_hidden_layers"] == 5 and len(cfg["layer_types"]) == 40
    assert list(cfg["reduced"]) == ["num_hidden_layers"]
    d = cells.architecture(cfg).dims_of(cfg)
    assert cells.architecture(cfg).layer_kinds(d) == [
        "conv_dense", "attn_sparse", "conv_sparse", "conv_sparse",
        "conv_sparse"]
    m = cfg["model"]
    assert (m["hidden"], m["mlp_dim"], m["expert_width"], m["experts"],
            m["experts_per_token"], m["kv_heads"], m["head_dim"],
            m["conv_taps"], m["vocab_size"]) == (
        2048, 11776, 1536, 64, 4, 8, 64, 3, 65536)
    slots = cfg["service"]["batch_sizes"][-1]
    assert cell.traffic["clients"] == slots * 5 // 4
