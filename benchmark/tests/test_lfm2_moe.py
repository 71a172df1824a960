"""``synthesis-offline`` at rehearsal width on the CPU: the cell runs end
to end (chunked admission through the one lane, the kernels in
interpret mode, slots re-used all through the window, the conv layers'
tails beside int8 keys and values in one slot's carry) and agrees with
``reference/lfm2_moe.py``; both controls (int8 weights under bfloat16,
int4 keys and values under int8) fail the limit they must; and a
comparison against the reference with its head norms, its ``C`` gate or
its plain convolution undone does not agree.

CPU readings at this width (PR 43) are in the rehearsal mix's
``limits_from``."""

import contextlib

import jax
import pytest

from benchmark import cells

CELL = "synthesis-offline"


def _limits():
    return cells.Cell(CELL, rehearsal=True).traffic["limits"]


def test_the_cell_rehearses_and_both_controls_fail(rehearse):
    seen, res = rehearse("--workload", CELL, "--seconds", "5", "--trace", "1",
                         "--seed", "5", "--control", "1")
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 8 and res["metrics"] == {}
    assert seen["programs_lowered_in_window"] == 0
    got = res["rehearsal_metrics"]
    # the program's counters reach the readers (the device's do not:
    # CPU): two conv layers' tails of 2 x 256 bfloat16 numbers, read and
    # written, are 4 KB a token; the keys and values grow with the
    # context (one KV head of 64 in 128 lanes: 260 B a token stored)
    tails = 2 * 2 * 2 * 256 * 2
    share = got["conv_state_share.synthesis"]["value"]
    total = got["cache_bytes_read_per_token.synthesis"]["value"] * 1e6
    assert 5.0 < share < 60.0
    # rows retire inside a dispatch and still emit: a little under
    assert total * share / 100.0 == pytest.approx(tails, rel=0.15)
    for name in ("gqa64_decode_attn_roofline", "attention_time_share.synthesis",
                 "expert_time_share.synthesis"):
        assert name not in got
    lim = _limits()
    for control in ("control", "control_kv"):
        failed = [k for k in lim if seen[f"{control}.{k}"] > lim[k]]
        assert failed == ["mean_abs_logprob_err"]


@pytest.fixture(scope="module")
def served():
    """One window of the rehearsal cell through ``GenerationService``:
    (configuration, seed, sampled finished requests, pad length)."""
    from benchmark import serving
    from benchmark.harness import configure_jax

    cell = cells.Cell(CELL, rehearsal=True)
    cfg = cell.config
    configure_jax(cell)
    seed = 3000000001
    service = serving.build_service(cell, seed, lambda *a: None)
    try:
        serving.warm(service, cell, seed, lambda *a: None)
        win = serving.closed_loop(service, cell, seed, 4.0, cfg["vocab_size"],
                                  lambda name: contextlib.nullcontext())
        serving.drain(win["reqs"], 120.0)
        samples = serving.sample_finished(
            win["reqs"], cell.traffic["check_requests"], seed)
        stats = service.stats()["engine"]
    finally:
        service.close()
    assert len(samples) == 16
    # prompts of up to four 16-token chunks on four slots used in turn
    assert max(len(s["ids"]) for s in samples) > 32
    assert stats["prefills"] > 4 * 4
    conv = stats["conv"]
    assert conv["chunk_tokens"] > 0 and conv["state_rows"] > 0
    # two conv layers beside the one attention layer, whose context
    # tokens are counted alone
    assert conv["layer_calls"] % 2 == 0
    assert stats["attention"]["kv_tokens_attended_share"] == 1.0
    # answers of 16-24 tokens at K = 4: rows retire inside a dispatch
    assert 0.5 < conv["state_rows_over_issued"] <= 1.0
    return cfg, seed, samples, 64 + 24


@pytest.mark.parametrize("reference,agrees", [
    ("as_published", True), ("no_qk_norm", False), ("no_c_gate", False),
    ("silu_after_the_conv", False)])
def test_the_served_window_is_lfm2_and_no_other_model(
        served, monkeypatch, reference, agrees):
    from benchmark.harness import judge
    from benchmark.reference.check_serve import serve_readings

    arch = cells.architecture(served[0])
    assert arch.layer_kinds(arch.dims_of(served[0])) == [
        "conv_dense", "attn_sparse", "conv_sparse"]
    patch = {
        "no_qk_norm": ("head_norm", lambda x, scale, eps: x),
        "no_c_gate": ("out_gate", lambda gate, c: c),
        "silu_after_the_conv": ("after_conv", jax.nn.silu),
    }.get(reference)
    if patch:
        monkeypatch.setattr(arch, *patch)
    assert judge(serve_readings(*served), _limits()) is agrees
