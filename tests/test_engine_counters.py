"""The engine's channel for what a model's layers sow: a routed expert
layer's counts ride the tail of the packed token buffer (fused and
staged chunks too), and the host's mirror counts the context tokens
each attention layer's window lets it read.  A model that sows nothing
keeps the (3, K, slots) buffer it always had."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlcomp_tpu.engine import _COUNT_GROUPS, DecodeEngine
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
    assert packed.shape == (3 * 2 * 2 + len(_COUNT_GROUPS["moe"]),)


# a router before the attention, ReLU experts, a first layer that rotates
# nothing and reads every key, two window layers
EARLY = {
    "name": "mixed_layer_lm", "vocab_size": 64, "hidden": 128, "head_dim": 64,
    "kv_heads": 1, "layer_types": ["full", "sliding", "sliding"],
    "heads_per_layer": [2, 2, 2], "mlp_layer_types": ["sparse"] * 3,
    "rope_full": {"rotary_dim": 0}, "rope_sliding": {"base": 1500000.0},
    "window": 8, "experts": 8, "experts_per_token": 2, "expert_width": 128,
    "early_router": True, "expert_gate": "relu", "dtype": "float32",
}


@pytest.mark.parametrize("kv_quant", [False, True])
def test_the_counts_by_layer_kind_and_by_call_class_are_the_hand_counts(
        kv_quant):
    """One request alone on three slots: 12 prompt tokens in a bucket of
    16 are two chunks of 8, then three dispatches of K = 2 steps hold
    12, 14 and 16 tokens of context at their issue."""
    model, params = _build({**EARLY, "kv_quant": kv_quant})
    k, layers, top_k, slots = 2, 3, 2, 3
    eng = DecodeEngine(model, {"params": params}, slots=slots,
                       prompt_buckets=(16,), max_new_cap=16,
                       steps_per_dispatch=k, prefill_chunk=8,
                       pipeline_depth=1)
    try:
        out = eng.submit(list(range(1, 13)), 3 * k).result(timeout=300)
        st = eng.stats()
        text = eng.metrics.render()
    finally:
        eng.close()
    assert len(out["ids"]) == 3 * k and st["pipeline"]["issued"] == 3
    held = 12 + 14 + 16
    att = st["attention"]
    assert att["by_kind"] == {
        "full": {"kv_tokens_attended": held, "kv_tokens_live": held},
        "window": {"kv_tokens_attended": 2 * 3 * 8,   # min(context, 8)
                   "kv_tokens_live": 2 * held},
    }
    assert att["kv_tokens_live"] == 3 * held
    assert att["kv_tokens_attended"] == held + 48
    # a 33-slot buffer rounds to one lane block, and the int8 walk moves
    # it whole: a layer a dispatch, window or none; no walk, no count
    assert att["kv_tokens_fetched"] == (3 * layers * 128 if kv_quant else 0)
    assert att["kv_fetch_live_share"] == (
        round((held + 48) / (9 * 128), 4) if kv_quant else None)
    moe = st["moe"]
    chunk_calls, step_calls = 2 * layers, 3 * k * layers
    assert moe["by_class"] == {
        "chunk": {"assignments": 8 * top_k * chunk_calls,
                  "assignments_held": 8 * top_k * chunk_calls,
                  "experts_touched": moe["by_class"]["chunk"]["experts_touched"],
                  "expert_layer_calls": chunk_calls},
        # a step routes every slot's token, the two empty rows' too
        "single_token": {
            "assignments": slots * top_k * step_calls,
            "assignments_held": slots * top_k * step_calls,
            "experts_touched":
                moe["by_class"]["single_token"]["experts_touched"],
            "expert_layer_calls": step_calls},
    }
    for key in ("assignments", "experts_touched", "expert_layer_calls"):
        assert moe[key] == sum(c[key] for c in moe["by_class"].values())
    assert chunk_calls <= moe["by_class"]["chunk"]["experts_touched"] \
        <= 8 * chunk_calls
    assert "mlcomp_engine_attention_kv_tokens_attended_window_total 48" in text
    assert f"mlcomp_engine_attention_kv_tokens_live_window_total {2 * held}" \
        in text
    assert f"mlcomp_engine_moe_chunk_expert_layer_calls_total {chunk_calls}" \
        in text


# ---- the int8 cache's single-token step appends inside the kernel ----

DENSE = {"name": "transformer_lm", "vocab_size": 64, "hidden": 64,
         "layers": 2, "heads": 2, "mlp_dim": 128, "dtype": "float32",
         "kv_quant": True}


def _loop_write_then_attend(real):
    """``decode_attention`` with the write it replaced: a call that
    carries ``append`` goes through ``conftest.loop_write_kv`` and then
    attends with the plain kernel."""
    from conftest import loop_write_kv

    def old(q, *caches, kv_stop=None, append=None, **kw):
        if append is not None:
            caches = loop_write_kv(caches, append, kv_stop - 1)
        out = real(q, *caches, kv_stop=kv_stop, **kw)
        return out if append is None else (out, *caches)

    return old


def _cache_row_writes(jaxpr):
    """(update-slices on a 4-D int8 buffer, ``decode_attention`` kernel
    calls) in a program, through its scans, loops and calls; a kernel's
    own body is not searched."""
    writes = kernels = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            kernels += eqn.params["name"] == "decode_attention"
            continue
        aval = eqn.outvars[0].aval if eqn.outvars else None
        if (eqn.primitive.name == "dynamic_update_slice"
                and aval.dtype == jnp.int8 and aval.ndim == 4):
            writes += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            w, k = _cache_row_writes(sub)
            writes, kernels = writes + w, kernels + k
    return writes, kernels


def _serve_three(model, params):
    """A run that admits A and B, decodes across several K = 4
    dispatches, retires B and gives its slot to C while A decodes."""
    import queue

    eng = DecodeEngine(model, {"params": params}, slots=2,
                       prompt_buckets=(16,), max_new_cap=24,
                       steps_per_dispatch=4, prefill_chunk=8)
    try:
        program = jax.make_jaxpr(eng._dispatch_fn())(
            eng.variables, eng._dstate)
        stream: "queue.Queue" = queue.Queue()
        fa = eng.submit([3, 14, 15, 9, 2, 7, 7, 30, 2, 1], 22,
                        logprobs=True, stream=stream)
        stream.get(timeout=300)                      # A is decoding
        rb = eng.submit([7, 3, 44, 5, 6], 3, logprobs=True).result(
            timeout=300)                             # B retires
        rc = eng.submit([11, 12, 13], 9, logprobs=True).result(timeout=300)
        ra = fa.result(timeout=300)
        slots_used = eng.stats()["slots"]
    finally:
        eng.close()
    assert slots_used == 2           # so C took the slot B left
    return [(r["ids"], r["logprobs"]) for r in (ra, rb, rc)], program


def test_the_kernels_append_equals_the_row_loop_it_replaced(monkeypatch):
    """Tokens and logprobs of a run whose single-token steps append
    inside ``decode_attention`` equal, bit for bit, a recording of the
    same run made through the old write; and the dispatch program
    holds no update-slice on an int8 cache buffer any more, where the
    recording's holds one a tensor a layer inside its row loop."""
    import mlcomp_tpu.ops.pallas.decode_attention as da

    model, params = _build(DENSE)
    got, program = _serve_three(model, params)
    monkeypatch.setattr(
        da, "decode_attention", _loop_write_then_attend(da.decode_attention)
    )
    recorded, old_program = _serve_three(model, params)
    assert got == recorded
    assert [len(ids) for ids, _ in got] == [22, 3, 9]
    layers = DENSE["layers"]
    assert _cache_row_writes(program.jaxpr) == (0, layers)
    assert _cache_row_writes(old_program.jaxpr) == (2 * layers, layers)


def test_kv_rows_written_is_live_rows_times_steps(monkeypatch):
    """One request alone on three slots, 2 x K tokens (a step a token,
    the first included): each of the two dispatches writes one row's
    token in each of its K steps, by the host's mirror and by the
    windows the kernels were handed."""
    import mlcomp_tpu.ops.pallas.decode_attention as da

    seen = []
    real = da.decode_attention

    def spy(q, k8, ks, v8, vs, kv_start=None, kv_stop=None, **kw):
        assert kw.get("append") is not None
        jax.debug.callback(
            lambda a, b: seen.append(int((np.asarray(a) < np.asarray(b)).sum())),
            kv_start, kv_stop, ordered=True,
        )
        return real(q, k8, ks, v8, vs, kv_start=kv_start, kv_stop=kv_stop,
                    **kw)

    monkeypatch.setattr(da, "decode_attention", spy)
    model, params = _build(DENSE)
    k = 4
    eng = DecodeEngine(model, {"params": params}, slots=3,
                       prompt_buckets=(16,), max_new_cap=16,
                       steps_per_dispatch=k, pipeline_depth=1)
    try:
        out = eng.submit([5, 6, 7, 8], 2 * k).result(timeout=300)
        st = eng.stats()
        text = eng.metrics.render()
    finally:
        eng.close()
    assert len(out["ids"]) == 2 * k
    att, issued = st["attention"], st["pipeline"]["issued"]
    assert issued == 2
    assert att["rows_total"] == 3 * issued
    assert att["kv_rows_written"] == att["rows_attended"] * k == 1 * k * issued
    # what the device saw: one kernel call a layer a step, one live row
    assert sum(seen) == att["kv_rows_written"] * DENSE["layers"]
    assert set(seen) == {1}
    assert (f"mlcomp_engine_attention_kv_rows_written_total "
            f"{att['kv_rows_written']}") in text


def test_kv_tokens_fetched_is_the_walks_count_over_the_mirror(monkeypatch):
    """One request alone, 100 prompt tokens left-padded to a bucket of
    256 in a 640-slot buffer walked as one granule: a step's window
    [156, cursor] touches lane blocks 1 and 2, so the kernel moves 256
    tokens a layer where the whole granule is 640.  The counter is
    ``kv_tokens_fetched`` over the host's mirror at issue, and over the
    windows the kernels were handed in each dispatch's first step."""
    import mlcomp_tpu.ops.pallas.decode_attention as da

    seen = []
    real = da.decode_attention

    def spy(q, k8, ks, v8, vs, kv_start=None, kv_stop=None, **kw):
        jax.debug.callback(
            lambda a, b: seen.append((np.asarray(a), np.asarray(b))),
            kv_start, kv_stop, ordered=True,
        )
        return real(q, k8, ks, v8, vs, kv_start=kv_start, kv_stop=kv_stop,
                    **kw)

    monkeypatch.setattr(da, "decode_attention", spy)
    model, params = _build(DENSE)
    k, layers = 4, DENSE["layers"]
    eng = DecodeEngine(model, {"params": params}, slots=3,
                       prompt_buckets=(256,), max_new_cap=300,
                       steps_per_dispatch=k, pipeline_depth=1)
    try:
        assert eng._kv_walk == (640, 640)
        out = eng.submit(list(range(1, 51)) * 2, 2 * k).result(timeout=300)
        st = eng.stats()
        text = eng.metrics.render()
    finally:
        eng.close()
    assert len(out["ids"]) == 2 * k and st["pipeline"]["issued"] == 2
    att = st["attention"]
    # a model that names no windows counts as ONE layer of attention
    assert att["kv_tokens_attended"] == 100 + 104
    assert att["kv_tokens_fetched"] == 2 * 256
    assert att["kv_tokens_fetched"] >= att["kv_tokens_attended"]
    assert att["kv_fetch_live_share"] == round(
        att["kv_tokens_attended"] / att["kv_tokens_fetched"], 4)
    # what the device saw: a kernel call a layer a step, every layer
    # the same windows; a dispatch's first call is what the mirror counts
    assert len(seen) == 2 * k * layers
    assert att["kv_tokens_fetched"] == sum(
        int(da.kv_tokens_fetched(*seen[d * k * layers], 640, 640).sum())
        for d in range(2)
    )
    assert (f"mlcomp_engine_attention_kv_tokens_fetched_total "
            f"{att['kv_tokens_fetched']}") in text


def test_no_dense_int8_walk_no_tokens_fetched():
    """A bfloat16 cache is not walked by the int8 kernel: the counter
    stays 0 and its share says nothing."""
    model, params = _build({**DENSE, "kv_quant": False})
    eng = _engine(model, params)
    try:
        assert eng._kv_walk is None
        eng.submit([3, 14, 15], 4).result(timeout=300)
        att = eng.stats()["attention"]
    finally:
        eng.close()
    assert att["kv_tokens_fetched"] == 0
    assert att["kv_fetch_live_share"] is None
    assert att["kv_tokens_attended"] > 0


# ---- a second group in the one channel: a retention layer's counts ----

RETENTION = {
    "name": "mixed_layer_lm", "vocab_size": 64, "hidden": 64, "head_dim": 16,
    "kv_heads": 2, "layer_types": ["retention"] * 2,
    "heads_per_layer": [4, 4], "mlp_layer_types": ["dense"] * 2,
    "mlp_dim": 128, "rope_full": {"base": 1000000.0}, "qk_norm": True,
    "dtype": "float32",
}


def test_a_retention_layers_counts_are_the_hand_counts():
    """One request alone on three slots: 12 prompt tokens in a bucket of
    16 are two chunks of 8 (four pads in the first), then three
    dispatches of K = 2 steps, the one live row through two layers."""
    from mlcomp_tpu.models.retention import COUNTS
    from mlcomp_tpu.ops.pallas.retention import state_bytes_moved

    assert tuple(n for n, _ in _COUNT_GROUPS["retention"]) == COUNTS
    model, params = _build(RETENTION)
    k, layers = 2, 2
    eng = DecodeEngine(model, {"params": params}, slots=3,
                       prompt_buckets=(16,), max_new_cap=16,
                       steps_per_dispatch=k, prefill_chunk=8,
                       pipeline_depth=1)
    try:
        _, packed = jax.eval_shape(
            eng._dispatch_fn(), eng.variables, eng._dstate)
        assert packed.shape == (3 * k * 3 + len(COUNTS),)
        out = eng.submit(list(range(1, 13)), 3 * k).result(timeout=300)
        st = eng.stats()
        text = eng.metrics.render()
    finally:
        eng.close()
    assert len(out["ids"]) == 3 * k and st["pipeline"]["issued"] == 3
    # greedy through the engine is greedy under the full forward pass
    seq = jnp.asarray([list(range(1, 13)) + out["ids"]])
    want = np.asarray(jnp.argmax(
        model.apply({"params": params}, seq)[0, 11:-1], -1)).tolist()
    assert out["ids"] == want
    rows = 3 * k * layers
    assert "moe" not in st
    assert st["retention"] == {
        "state_rows": rows,
        "state_bytes": state_bytes_moved(rows, 2, 16),
        "chunk_tokens": 12 * layers,
        "layer_calls": (2 + 3 * k) * layers,
        "state_rows_over_issued": 1.0,
    }
    assert st["attention"]["kv_rows_written"] == 3 * k
    assert st["attention"]["kv_tokens_live"] == 0
    assert st["attention"]["kv_tokens_attended_share"] is None
    assert f"mlcomp_engine_retention_state_rows_total {rows}" in text
    assert f"mlcomp_engine_retention_chunk_tokens_total {12 * layers}" in text
    assert "mlcomp_engine_moe_" not in text
