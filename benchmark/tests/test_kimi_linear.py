"""``reasoning-offline`` at rehearsal width on the CPU: the cell runs end
to end (chunked admission through the one lane, both kernels in
interpret mode, slots re-used all through the window, BOTH caches in
one slot's carry) and agrees with ``reference/kimi_linear.py``; the
int8-weights control fails the limit it must; and a comparison against
the reference with its erase, its channel decay or its sigmoid router
undone does not agree.  (A rotated shared key moves these 88-token
contexts by less than bfloat16 does at this width: that control is
held at float32, ``tests/test_mixed_layer_lm.py``.)

CPU readings at this width (PR 41) are in the rehearsal mix's
``limits_from``."""

import contextlib

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells

CELL = "reasoning-offline"


def _limits():
    return cells.Cell(CELL, rehearsal=True).traffic["limits"]


def test_the_cell_rehearses_and_its_control_fails(rehearse):
    seen, res = rehearse("--workload", CELL, "--seconds", "5", "--trace", "1",
                         "--seed", "5", "--control", "1")
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 8 and res["metrics"] == {}
    assert seen["programs_lowered_in_window"] == 0
    got = res["rehearsal_metrics"]
    # the program's counters reach the readers (the device's do not:
    # CPU): four KDA layers of 4 heads of 16 x 16 a token, plus the
    # latent layer's one block of 96 slots x 128 lanes, bfloat16
    state = 4 * 4 * 16 * 16 * 4 * 2
    latent = 96 * 128 * 2
    assert got["cache_bytes_per_token.reasoning"]["value"] == pytest.approx(
        (state + latent) / 1e6, rel=0.05)
    assert got["latent_bytes_share.reasoning"]["value"] == pytest.approx(
        100.0 * latent / (state + latent), rel=0.05)
    for name in ("kda_step_roofline", "latent_decode_roofline",
                 "kda_time_share.reasoning",
                 "latent_attn_time_share.reasoning"):
        assert name not in got
    lim = _limits()
    failed = [k for k in lim if seen[f"control.{k}"] > lim[k]]
    assert failed == ["mean_abs_logprob_err"]
    assert "control_kv.max_logit_gap" not in seen   # nothing states a rounding


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
    kda, latent = stats["kda"], stats["latent"]
    assert kda["chunk_tokens"] > 0 and kda["state_rows"] > 0
    assert latent["chunk_tokens"] > 0 and latent["tokens_attended"] > 0
    # four KDA layers beside one latent layer, the same rows and chunks
    assert kda["chunk_tokens"] == 4 * latent["chunk_tokens"]
    assert kda["layer_calls"] == 4 * latent["layer_calls"]
    # answers of 16-24 tokens at K = 4: rows retire inside a dispatch
    assert 0.5 < kda["state_rows_over_issued"] <= 1.0
    return cfg, seed, samples, 64 + 24


@pytest.mark.parametrize("reference,agrees", [
    ("as_published", True), ("no_erase", False), ("a_scalar_decay", False),
    ("softmax_router", False)])
def test_the_served_window_is_kimi_linear_and_no_other_model(
        served, monkeypatch, reference, agrees):
    from benchmark.harness import judge
    from benchmark.reference.check_serve import serve_readings

    arch = cells.architecture(served[0])
    assert arch.layer_kinds(arch.dims_of(served[0])) == [
        "kda_dense", "kda", "kda", "latent", "kda"]
    patch = {
        "no_erase": ("erased", lambda state, k: jnp.zeros_like(k)),
        "a_scalar_decay": ("log_decay", lambda a_log, pre: jnp.mean(
            -jnp.exp(a_log)[:, None] * jax.nn.softplus(pre), -1,
            keepdims=True) + 0.0 * pre),
        "softmax_router": ("router_scores",
                           lambda logits: jax.nn.softmax(logits, -1)),
    }.get(reference)
    if patch:
        monkeypatch.setattr(arch, *patch)
    assert judge(serve_readings(*served), _limits()) is agrees
