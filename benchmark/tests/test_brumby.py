"""``continuation-offline`` at rehearsal width on the CPU: the cell runs
end to end (chunked admission through the one lane, the kernel in
interpret mode, slots re-used all through the window) and agrees with
``reference/brumby.py``; the int8-weights control fails the limit it
must; and a comparison against the reference with its gates, its
normaliser or its degree undone does not agree.

CPU readings at this width (PR 37) are in the rehearsal mix's
``limits_from``."""

import contextlib

import jax.numpy as jnp
import pytest

from benchmark import cells

CELL = "continuation-offline"


def _limits():
    return cells.Cell(CELL, rehearsal=True).traffic["limits"]


def test_the_cell_rehearses_and_its_control_fails(rehearse):
    seen, res = rehearse("--workload", CELL, "--seconds", "5", "--trace", "1",
                         "--seed", "5", "--control", "1")
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 8 and res["metrics"] == {}
    assert seen["programs_lowered_in_window"] == 0
    got = res["rehearsal_metrics"]
    # the program's counters reach the reader (the device's do not: CPU):
    # two layers, two KV heads of 16, one pass a token
    a_slot = 2 * 2 * (9 * 16) * (16 + 1) * 4
    assert got["state_bytes_per_token.continuation"]["value"] == pytest.approx(
        2 * a_slot / 1e6, rel=0.05)
    assert "retention_step_roofline" not in got
    assert "retention_time_share.continuation" not in got
    lim = _limits()
    failed = [k for k in lim if seen[f"control.{k}"] > lim[k]]
    assert failed == ["mean_abs_logprob_err"]
    assert "control_kv.max_logit_gap" not in seen   # no keys and values


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
    ret = stats["retention"]
    assert ret["chunk_tokens"] > 0 and ret["state_rows"] > 0
    # answers of 12-16 tokens at K = 4: rows retire inside a dispatch
    assert 0.5 < ret["state_rows_over_issued"] <= 1.0
    return cfg, seed, samples, 64 + 16


@pytest.mark.parametrize("reference,agrees", [
    ("as_published", True), ("gates_of_one", False),
    ("no_normaliser", False), ("degree_one", False)])
def test_the_served_window_is_brumby_and_no_other_model(
        served, monkeypatch, reference, agrees):
    from benchmark.harness import judge
    from benchmark.reference.check_serve import serve_readings

    arch = cells.architecture(served[0])
    assert arch.layer_kinds(arch.dims_of(served[0])) == ["retention"] * 2
    patch = {
        "gates_of_one": ("log_gate", jnp.zeros_like),
        "no_normaliser": ("normalised", lambda num, den: num),
        "degree_one": ("power", lambda dots: dots),
    }.get(reference)
    if patch:
        monkeypatch.setattr(arch, *patch)
    assert judge(serve_readings(*served), _limits()) is agrees
