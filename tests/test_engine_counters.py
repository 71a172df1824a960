"""The engine's channel for what a model's layers sow: a routed expert
layer's counts ride the tail of the packed token buffer (fused and
staged chunks too), and the host's mirror counts the context tokens
each attention layer's window lets it read.  A model that sows nothing
keeps the (3, K, slots) buffer it always had."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlcomp_tpu.engine import N_COUNTS, DecodeEngine
from mlcomp_tpu.models import create_model
from mlcomp_tpu.train.state import init_model

MIXED = {
    "name": "mixed_layer_lm", "vocab_size": 64, "hidden": 128, "head_dim": 64,
    "kv_heads": 1, "layer_types": ["full", "sliding", "full"],
    "heads_per_layer": [2, 3, 2],
    "mlp_layer_types": ["dense", "sparse", "sparse"], "mlp_dim": 128,
    "rope_full": {"base": 500000.0, "rotary_dim": 32, "factor": 128.0,
                  "original_max": 64, "attention_factor": 1.4852},
    "rope_sliding": {"base": 10000.0}, "window": 8, "head_gate": True,
    "experts": 8, "experts_per_token": 2, "experts_held": [0, 4],
    "routed_scale": 2.5, "expert_width": 128, "shared_width": 128,
    "dtype": "float32",
}


def _build(cfg, seed=0):
    model = create_model(dict(cfg))
    prompt = jnp.asarray(np.random.RandomState(seed).randint(1, 64, (1, 8)))
    params, _ = init_model(model, {"x": prompt}, jax.random.PRNGKey(seed))
    return model, params


def _engine(model, params, **kw):
    return DecodeEngine(model, {"params": params}, slots=2,
                        prompt_buckets=(16,), max_new_cap=16,
                        steps_per_dispatch=2, prefill_chunk=8, **kw)


@pytest.mark.parametrize("kv_quant", [False, True])
@pytest.mark.parametrize("fused", [True, False])
def test_a_mixed_layer_model_is_served_and_counted(kv_quant, fused):
    model, params = _build({**MIXED, "kv_quant": kv_quant})
    eng = _engine(model, params, fused_admission=fused)
    try:
        ids_a, ids_b = [3, 14, 15, 9, 2, 7, 7, 30, 2, 1], [7, 3, 44, 5, 6]
        fa = eng.submit(ids_a, 14)
        fb = eng.submit(ids_b, 12)
        out_a, out_b = fa.result(timeout=300), fb.result(timeout=300)
        st = eng.stats()
    finally:
        eng.close()
    # greedy through the engine (chunked prefill, cursors, windows) is
    # greedy under the full forward pass, no cache
    for ids, out in ((ids_a, out_a), (ids_b, out_b)):
        seq = jnp.asarray([ids + out["ids"]])
        logits = model.apply({"params": params}, seq)
        want = np.asarray(jnp.argmax(logits[0, len(ids) - 1:-1], -1)).tolist()
        if not kv_quant:
            assert out["ids"] == want
    moe = st["moe"]
    calls = moe["expert_layer_calls"]
    # two sparse layers a model call; every call routes 2 experts a token
    assert calls > 0 and calls % 2 == 0
    assert moe["assignments"] >= calls * 2
    assert 0 < moe["assignments_held"] < moe["assignments"]
    assert 0 < moe["experts_touched"] <= calls * 4
    assert moe["experts_touched_share"] == pytest.approx(
        moe["experts_touched"] / (calls * 4), abs=1e-4)
    att = st["attention"]
    # contexts pass the window of 8 in one layer of three
    assert 0 < att["kv_tokens_attended"] < att["kv_tokens_live"]
    assert att["kv_tokens_attended_share"] > 1 / 3


def test_a_model_that_sows_nothing_keeps_its_packed_buffer():
    model, params = _build({
        "name": "transformer_lm", "vocab_size": 64, "hidden": 64, "layers": 2,
        "heads": 2, "mlp_dim": 128, "dtype": "float32"})
    eng = _engine(model, params)
    try:
        out, packed = jax.eval_shape(
            eng._dispatch_fn(), eng.variables, eng._dstate)
        assert packed.shape == (3, 2, 2)
        eng.submit([3, 14, 15], 6).result(timeout=300)
        st = eng.stats()
    finally:
        eng.close()
    assert "moe" not in st
    att = st["attention"]
    assert att["kv_tokens_attended"] == att["kv_tokens_live"] > 0


def test_the_counts_ride_the_tail_of_the_packed_buffer():
    model, params = _build(MIXED)
    eng = _engine(model, params)
    try:
        _, packed = jax.eval_shape(
            eng._dispatch_fn(), eng.variables, eng._dstate)
    finally:
        eng.close()
    assert packed.shape == (3 * 2 * 2 + N_COUNTS,)
