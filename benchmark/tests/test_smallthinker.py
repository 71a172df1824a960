"""``mixed-length-offline`` at rehearsal width on the CPU: the served
path (chunked prefill in chunks of at most the window, then decode
through the int8 cache, contexts of three times the window of 16)
agrees with ``reference/smallthinker.py`` on logits; a comparison in
which the router reads the post-attention state, the experts gate with
SiLU, or the global layer rotates, does not; the int8 weights and the
int4 keys and values each fail one of the cell's limits.

CPU readings at this width (PR 35, seeds 5, 11 and 3000000001, 16
requests a run) are in the rehearsal mix's ``limits_from``."""

import contextlib

import pytest

from benchmark import cells

CELL = "mixed-length-offline"


def _limits():
    return cells.Cell(CELL, rehearsal=True).traffic["limits"]


def test_the_cell_rehearses_and_its_controls_fail(rehearse):
    seen, res = rehearse("--workload", CELL, "--seconds", "5", "--trace", "1",
                         "--seed", "5", "--control", "1")
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 8 and res["metrics"] == {}
    assert seen["programs_lowered_in_window"] == 0
    got = res["rehearsal_metrics"]
    # the program's counters reach the readers (the device's do not: CPU)
    assert 25.0 <= got["experts_touched_share.mixedlen"]["value"] <= 100.0
    assert 50.0 < got["kv_tokens_attended_share.mixedlen"]["value"] < 100.0
    # one window layer beside one full layer: it reads less than half
    assert 10.0 < got["window_kv_read_share.mixedlen"]["value"] < 50.0
    assert "grouped_matmul_roofline_by_class" not in got
    assert "attention_time_share.mixedlen" not in got
    lim = _limits()
    for control in ("control", "control_kv"):   # int8 weights, int4 KV
        failed = [k for k in lim if seen[f"{control}.{k}"] > lim[k]]
        assert failed, f"{control} has to fail one of the cell's numbers"


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
    # contexts reach three times the window of 16, in chunks of 16
    assert max(len(s["ids"]) + len(s["out"]) for s in samples) > 48
    assert cfg["service"]["prefill_chunk"] <= cfg["sliding_window_size"] == 16
    moe, kinds = stats["moe"], stats["attention"]["by_kind"]
    assert moe["by_class"]["chunk"]["expert_layer_calls"] > 0
    assert moe["by_class"]["single_token"]["expert_layer_calls"] > 0
    assert kinds["window"]["kv_tokens_attended"] \
        < kinds["window"]["kv_tokens_live"] == kinds["full"]["kv_tokens_live"]
    return cfg, seed, samples, 48 + 16


def _as_another_model(arch, name, monkeypatch):
    """Undo one of the three things that make the model SmallThinker."""
    import jax

    if name == "router_after_attention":
        monkeypatch.setattr(arch, "router_input", lambda h, u: u)
    elif name == "silu_gate":
        monkeypatch.setattr(arch, "relu", jax.nn.silu)
    elif name == "global_layer_rotates":
        real = arch.dims_of
        monkeypatch.setattr(arch, "dims_of", lambda cfg: {
            **real(cfg), "rotates": {"sliding": True, "full": True}})


@pytest.mark.parametrize("reference,agrees", [
    ("as_published", True), ("router_after_attention", False),
    ("silu_gate", False), ("global_layer_rotates", False)])
def test_the_served_window_is_smallthinker_and_no_other_model(
        served, monkeypatch, reference, agrees):
    from benchmark.harness import judge
    from benchmark.reference.check_serve import serve_readings

    arch = cells.architecture(served[0])
    assert arch.layer_kinds(arch.dims_of(served[0])) == ["full", "sliding"]
    _as_another_model(arch, reference, monkeypatch)
    assert judge(serve_readings(*served), _limits()) is agrees
