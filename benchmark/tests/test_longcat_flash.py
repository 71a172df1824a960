"""``document-qa-offline`` at rehearsal width on the CPU: the cell runs
end to end (chunked admission through the one lane, the latent kernel
and the grouped matmul in interpret mode, slots re-used all through the
window, four latent leaves in one slot's carry and nothing else) and agrees with ``reference/longcat_flash.py``; the int8-weights
control fails the limit it must; the reference's layout is the
program's; and a comparison against the reference with its rotation,
its latent scale, its raw gates or its zero experts undone does not
agree.

CPU readings at this width (PR 47) are in the rehearsal mix's
``limits_from``."""

import contextlib

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells
from benchmark import weights as W

CELL = "document-qa-offline"


def _limits():
    return cells.Cell(CELL, rehearsal=True).traffic["limits"]


@pytest.mark.parametrize("rehearsal", [True, False], ids=["tiny", "cell"])
def test_the_references_layout_is_the_programs(rehearsal):
    """``program_layer`` / ``program_top`` against ``model.init``'s own
    shapes (``weights.check_layout``), at both widths; shapes only."""
    from mlcomp_tpu.models import create_model

    cfg = cells.Cell(CELL, rehearsal=rehearsal).config
    arch = cells.architecture(cfg)
    d = arch.dims_of(cfg)
    model = create_model(dict(cfg["model"]))
    params = jax.eval_shape(
        lambda: W.program_params(arch, 7, d, jnp.bfloat16))
    abstract = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    W.check_layout(params, abstract)
    assert arch.layer_kinds(d) == ["shortcut"] * d["layers"]
    assert d["mixers"] == len(model.attention_windows()) == 2 * d["layers"]
    # every drawn matrix has its contraction axes; the float32 router,
    # its bias and the norms are never rounded
    leaves = jax.eval_shape(lambda: arch.layer_weights(
        W.seed_key(7), 0, d, jnp.bfloat16, "shortcut"))
    unrounded = sorted(set(leaves) - set(arch.CONTRACT_AXES))
    assert unrounded == sorted(
        ["router", "router_bias"] + [f"{n}_{b}" for b in arch.BLOCKS for n in (
            "attn_norm", "q_norm", "kv_norm", "mlp_norm")])
    if not rehearsal:
        n = sum(x.size for x in jax.tree.leaves(params))
        assert abs(n * 2 / 1e9 - 10.35) < 0.01   # bytes.reckoned


def test_the_cell_rehearses_and_its_control_fails(rehearse):
    seen, res = rehearse("--workload", CELL, "--seconds", "5", "--trace", "1",
                         "--seed", "5", "--control", "1")
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 8 and res["metrics"] == {}
    assert seen["programs_lowered_in_window"] == 0
    got = res["rehearsal_metrics"]
    # the program's counters reach the readers (the device's do not:
    # CPU): 8 zero experts of 24 outputs, and a step's latent bytes: four
    # attention blocks' one block of 96 slots x 128 lanes, bfloat16
    assert 20.0 < got["zero_expert_share.docqa"]["value"] < 50.0
    assert got["latent_bytes_per_token.docqa"]["value"] == pytest.approx(
        4 * 96 * 128 * 2 / 1e6, rel=0.05)
    assert 0 < got["experts_touched_share.docqa"]["value"] <= 100.0
    for name in ("mla_decode_roofline.docqa", "latent_chunk_time_share.docqa",
                 "latent_attn_time_share.docqa"):
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
    latent, moe = stats["latent"], stats["moe"]
    assert latent["chunk_tokens"] > 0 and latent["tokens_attended"] > 0
    # two attention blocks a layer beside one routed block
    assert latent["layer_calls"] == 2 * moe["expert_layer_calls"]
    assert 0 < moe["zero_assignments"] < moe["assignments"]
    return cfg, seed, samples, 64 + 24


@pytest.mark.parametrize("reference,agrees", [
    ("as_published", True), ("unrotated", False), ("no_kv_scale", False),
    ("renormalised_gates", False), ("no_zero_experts", False)])
def test_the_served_window_is_longcat_flash_and_no_other_model(
        served, monkeypatch, reference, agrees):
    from benchmark.harness import judge
    from benchmark.reference.check_serve import serve_readings

    arch = cells.architecture(served[0])
    patch = {
        "unrotated": ("rotate", lambda x, positions, d: x),
        "no_kv_scale": ("kv_scale", lambda d: 1.0),
        "renormalised_gates": ("gates", lambda chosen, d: chosen / jnp.sum(
            chosen, -1, keepdims=True) * d["routed_scale"]),
        "no_zero_experts": ("zero_part",
                            lambda u, weight, d: jnp.zeros_like(u)),
    }.get(reference)
    if patch:
        monkeypatch.setattr(arch, *patch)
    assert judge(serve_readings(*served), _limits()) is agrees
