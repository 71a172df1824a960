"""``rollout-offline`` at rehearsal width on the CPU: the program agrees
with ``reference/laguna.py``; a comparison in which the window is
ignored, or the expert layer is a dense mixture, does not; the int8
weights and the int4 keys and values each fail one of the cell's limits.

CPU readings at this width (PR 28, seeds 5 and 3000000001): sound
max_logit_gap 1.31-1.72 / mean_abs_logprob_err 0.047-0.060; int8-weight
control 1.31-1.47 / 0.084-0.097; int4-KV control 3.0-3.4 / 0.55.  Top-2
routing over 8 experts flips on near-ties between the program's
bfloat16 / int8-KV activations and the float32 reference, in sound runs
and controls alike, so the gap does not separate them and the mean
error does."""

import contextlib

import pytest

from benchmark import cells

CELL = "rollout-offline"


def _limits():
    return cells.Cell(CELL, rehearsal=True).traffic["limits"]


def test_the_cell_rehearses_and_its_controls_fail(rehearse):
    seen, res = rehearse("--workload", CELL, "--seconds", "4", "--trace", "1",
                         "--seed", "5", "--control", "1")
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 8 and res["metrics"] == {}
    assert seen["programs_lowered_in_window"] == 0
    got = res["rehearsal_metrics"]
    # the program's counters reach the readers (the device's do not: CPU)
    assert 25.0 <= got["experts_touched_share.rollout"]["value"] <= 100.0
    assert 50.0 < got["kv_tokens_attended_share.rollout"]["value"] < 100.0
    assert "grouped_matmul_roofline" not in got
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
        win = serving.closed_loop(service, cell, seed, 3.0, cfg["vocab_size"],
                                  lambda name: contextlib.nullcontext())
        serving.drain(win["reqs"], 120.0)
        samples = serving.sample_finished(win["reqs"], 6, seed)
        moe = service.stats()["engine"]["moe"]
    finally:
        service.close()
    assert len(samples) == 6
    # contexts cross the window of 16, and the expert layer counted
    assert max(len(s["ids"]) + len(s["out"]) for s in samples) > 32
    assert moe["assignments"] > moe["assignments_held"] > 0
    assert 0 < moe["experts_touched_share"] <= 1
    return cfg, seed, samples, 32 + 32


def test_the_served_window_agrees_with_the_reference(served):
    from benchmark.harness import judge
    from benchmark.reference.check_serve import serve_readings

    arch = cells.architecture(served[0])
    assert arch.layer_kinds(arch.dims_of(served[0])) == [
        "dense_full", "sparse_sliding", "sparse_full"]
    assert judge(serve_readings(*served), _limits()) is True


def test_with_the_window_ignored_it_is_not_correct(served, monkeypatch):
    from benchmark.harness import judge
    from benchmark.reference.check_serve import serve_readings

    arch = cells.architecture(served[0])
    real = arch.dims_of
    monkeypatch.setattr(arch, "dims_of",
                        lambda cfg: {**real(cfg), "window": 1 << 20})
    assert judge(serve_readings(*served), _limits()) is False


def test_with_the_experts_a_dense_mixture_it_is_not_correct(
        served, monkeypatch):
    """Every expert on every token at one weight: a dense MLP in the
    routed layer's place."""
    import jax.numpy as jnp

    from benchmark.harness import judge
    from benchmark.reference.check_serve import serve_readings

    arch = cells.architecture(served[0])
    monkeypatch.setattr(
        arch, "route",
        lambda u, router, d: jnp.full(
            u.shape[:-1] + (d["experts"],), d["routed_scale"] / d["experts"],
            jnp.float32))
    assert judge(serve_readings(*served), _limits()) is False
