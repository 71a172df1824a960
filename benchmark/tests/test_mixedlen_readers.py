"""The readers ``mixed-length-offline`` adds, on hand-made ops and
``stats()``: a grouped-matmul call is costed by its own class, the
window layers' share of the KV read comes from the counts by layer
kind, and a program that counts neither gives no number."""

import pytest

from benchmark import cells

LAYOUT = "{1,0:T(8,128)(2,1)}"


def _op(rows, n, k, stacks, tm=16, experts=64):
    """A grouped-matmul call as the profiler names it: the result, then
    the operands (a group a tile, the tiles used, the rows, the weight
    stacks)."""
    w = f"bf16[{experts},{k},{n}]{{2,1,0:T(8,128)(2,1)}}"
    operands = [f"s32[{rows // tm}]{{0:T(128)}} %tg", "s32[1]{0:T(128)} %used",
                f"bf16[{rows},{k}]{LAYOUT} %x"] + [
                    f"{w} %w{i}" for i in range(stacks)]
    return (f"%grouped_matmul.{stacks} = bf16[{rows},{n}]{LAYOUT} "
            f"custom-call({', '.join(operands)}), "
            'custom_call_target="tpu_custom_call"')


# 32 slots x 6 experts a token, and chunks of 2,048 tokens, laid out in
# 16-row tiles over 64 experts: 192 + 64 x 15 and 12,288 + 64 x 15 rows
STEP_ROWS, CHUNK_ROWS = 1152, 13248
CLASSES = {
    "single_token": {"assignments": 192.0 * 800, "assignments_held": 192.0 * 800,
                     "experts_touched": 61.0 * 800, "expert_layer_calls": 800.0},
    "chunk": {"assignments": 12288.0 * 96, "assignments_held": 12288.0 * 96,
              "experts_touched": 64.0 * 96, "expert_layer_calls": 96.0},
}


@pytest.mark.parametrize("rows,name,held,touched", [
    (STEP_ROWS, "single_token", 192.0, 61.0),
    (CHUNK_ROWS, "chunk", 12288.0, 64.0),
])
def test_a_call_is_costed_by_its_own_class(rows, name, held, touched):
    mod = cells.roofline("grouped_matmul_by_class")
    front, back = _op(rows, 768, 2560, 2), _op(rows, 2560, 768, 1)
    assert mod.match(front) and mod.class_of(front, CLASSES) == name
    assert mod.class_of(back, CLASSES) == name
    flops, nbytes = mod.cost(front, {"moe_classes": CLASSES})
    assert flops == 2.0 * held * 2560 * 768 * 2
    assert nbytes == 2 * (held * (2560 + 768) + touched * 2560 * 768 * 2)
    # the window's mean rows would cost a step at six times its own
    mean = (192.0 * 800 + 12288.0 * 96) / 896
    old, _ = cells.roofline("grouped_matmul").cost(
        front, {"moe_rows_per_call": mean, "moe_experts_per_call": 61.3})
    assert old > 6 * 2.0 * 192 * 2560 * 768 * 2


def test_a_window_without_chunks_has_one_class():
    mod = cells.roofline("grouped_matmul_by_class")
    only = {**CLASSES, "chunk": dict.fromkeys(CLASSES["chunk"], 0.0)}
    assert mod.class_of(_op(CHUNK_ROWS, 768, 2560, 2), only) == "single_token"


def _stats(attn=None, moe=None):
    return {"engine": {"attention": attn or {}, "moe": moe or {}}}


def test_the_counts_by_kind_and_by_class_are_run_deltas():
    read = cells.layer_reader("window_kv_read_share.mixedlen")
    by = lambda w, f: {"by_kind": {                       # noqa: E731
        "window": {"kv_tokens_attended": w, "kv_tokens_live": 2 * w},
        "full": {"kv_tokens_attended": f, "kv_tokens_live": f}}}
    ctx = {"stats0": _stats(by(100, 50)), "stats1": _stats(by(700, 250))}
    assert read("window_kv_read_share.mixedlen", ctx) == pytest.approx(75.0)
    # a program that does not split its counts: nothing to read, no raise
    old = {"kv_tokens_attended": 5, "kv_tokens_live": 9}
    assert read("window_kv_read_share.mixedlen",
                {"stats0": _stats(old), "stats1": _stats(old)}) is None
    assert read("window_kv_read_share.mixedlen", {}) is None

    from benchmark.layer_metrics.grouped_matmul_roofline_by_class import (
        classes, read as roofline)
    before = {"by_class": {k: {m: v / 2 for m, v in c.items()}
                           for k, c in CLASSES.items()}}
    got = classes({"stats0": _stats(moe=before),
                   "stats1": _stats(moe={"by_class": CLASSES})})
    assert got["chunk"]["expert_layer_calls"] == 48.0
    assert got["single_token"]["assignments"] == 192.0 * 400
    flat = {"assignments": 1.0, "expert_layer_calls": 1.0}
    assert classes({"stats1": _stats(moe=flat)}) is None
    assert roofline("grouped_matmul_roofline_by_class",
                    {"trace": None, "peaks": {}, "stats1": _stats(moe=flat)}
                    ) is None


def test_attention_time_share_sums_the_attention_kernels():
    read = cells.layer_reader("attention_time_share.mixedlen")

    class FakeTrace:
        devices = ["/device:TPU:0"]
        ops = [("%decode_attention.5 = bf16[32,28,128]", 0.0, 3e8),
               ("%decode_attention_chunk.9 = bf16[1,4,224,128]", 3e8, 4e8),
               ("%flash_fwd_kernel.2 = bf16[1,28,2048,128]", 4e8, 5e8),
               ("%grouped_matmul.3 = bf16[1152,768]", 5e8, 9e8),
               ("%fusion.7 = f32[8]", 9e8, 1e9)]

        def kernel_events(self, match):
            return [e for e in self.ops if match(e[0])]

        def busy_s(self):
            return 1.0

    assert read("attention_time_share.mixedlen", {"trace": FakeTrace()}
                ) == pytest.approx(50.0)
    assert read("attention_time_share.mixedlen", {"trace": None}) is None


def test_fused_dispatch_ms_is_the_mean_fused_program():
    import re

    read = cells.layer_reader("fused_dispatch_ms.mixedlen")

    class FakeTrace:
        spans = [("jit_fused(123)", 0.0, 100e6), ("jit_fused(123)", 100e6, 210e6),
                 ("jit_dispatch(7)", 210e6, 240e6), ("jit_insert(9)", 240e6, 241e6)]

        def module_spans(self, pattern):
            return [s for s in self.spans if re.search(pattern, s[0])]

    assert read("fused_dispatch_ms.mixedlen", {"trace": FakeTrace()}
                ) == pytest.approx(105.0)
    FakeTrace.spans = FakeTrace.spans[2:]
    assert read("fused_dispatch_ms.mixedlen", {"trace": FakeTrace()}) is None
    assert read("fused_dispatch_ms.mixedlen", {"trace": None}) is None
