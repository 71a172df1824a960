"""The engine's channel for what a model's layers sow: a routed expert
layer's counts ride the tail of the packed token buffer (fused and
staged chunks too), and the host's mirror counts the context tokens
each attention layer's window lets it read.  A model that sows nothing
keeps the (3, K, slots) buffer it always had."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlcomp_tpu.engine import DecodeEngine
from mlcomp_tpu.models import create_model
from mlcomp_tpu.models.moe import COUNTS as MOE_COUNTS
from mlcomp_tpu.train.state import init_model

# what every per-slot state kind counts, in its vector's order
STATE_COUNTS = ("state_rows", "state_bytes", "chunk_tokens", "layer_calls")

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
    # a fused dispatch's chunk and steps, a staged chunk, a plain
    # dispatch: at most 8 rows an expert a call, so every expert a call
    # reached is one 16-row tile, in either class
    by_class = moe["by_class"]
    assert moe["tile_rows"] == 16 * moe["experts_touched"] == sum(
        c["tile_rows"] for c in by_class.values())
    for c in by_class.values():
        assert c["tile_rows"] == 16 * c["experts_touched"] > 0
        assert c["assignments_held"] <= c["tile_rows"]
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
    assert packed.shape == (3 * 2 * 2 + len(MOE_COUNTS.entries),)


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
    assert att["kv_trips"] == (3 * layers if kv_quant else 0)
    moe = st["moe"]
    chunk_calls, step_calls = 2 * layers, 3 * k * layers
    assert moe["by_class"] == {
        "chunk": {"assignments": 8 * top_k * chunk_calls,
                  "assignments_held": 8 * top_k * chunk_calls,
                  "experts_touched": moe["by_class"]["chunk"]["experts_touched"],
                  "expert_layer_calls": chunk_calls,
                  "tile_rows":
                      16 * moe["by_class"]["chunk"]["experts_touched"],
                  "zero_assignments": 0},
        # a step routes every slot's token, the two empty rows' too
        "single_token": {
            "assignments": slots * top_k * step_calls,
            "assignments_held": slots * top_k * step_calls,
            "experts_touched":
                moe["by_class"]["single_token"]["experts_touched"],
            "expert_layer_calls": step_calls,
            "tile_rows":
                16 * moe["by_class"]["single_token"]["experts_touched"],
            "zero_assignments": 0},
    }
    for key in ("assignments", "experts_touched", "expert_layer_calls",
                "tile_rows"):
        assert moe[key] == sum(c[key] for c in moe["by_class"].values())
    assert chunk_calls <= moe["by_class"]["chunk"]["experts_touched"] \
        <= 8 * chunk_calls
    assert "mlcomp_engine_attention_kv_tokens_attended_window_total 48" in text
    assert f"mlcomp_engine_attention_kv_tokens_live_window_total {2 * held}" \
        in text
    assert f"mlcomp_engine_moe_chunk_expert_layer_calls_total {chunk_calls}" \
        in text
    for name, rows in (("tile_rows", moe["tile_rows"]),
                       ("chunk_tile_rows", moe["by_class"]["chunk"]["tile_rows"])):
        assert f"mlcomp_engine_moe_{name}_total {int(rows)}" in text


def test_the_tile_counts_close_the_expert_layers_vector():
    """The nine entries every reader indexes keep their places; the two
    tile counts come after them, the two counts of assignments to
    zero-compute experts after those, and the classes are the entries
    with a ``chunk_`` twin."""
    assert MOE_COUNTS.name == "moe"
    names = list(MOE_COUNTS.names)
    assert names == [
        "assignments", "assignments_held", "experts_touched",
        "expert_layer_calls", "experts_held", "chunk_assignments",
        "chunk_assignments_held", "chunk_experts_touched",
        "chunk_expert_layer_calls", "tile_rows", "chunk_tile_rows",
        "zero_assignments", "chunk_zero_assignments"]
    assert tuple(n[len("chunk_"):] for n in names if n.startswith("chunk_")) \
        == tuple(names[:4]) + ("tile_rows", "zero_assignments")


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
        int(da.kv_walk_counts(*seen[d * k * layers], 640, 640)[0].sum())
        for d in range(2)
    )
    assert (f"mlcomp_engine_attention_kv_tokens_fetched_total "
            f"{att['kv_tokens_fetched']}") in text
    # and the trips it made for them: one granule, one trip a dispatch
    assert att["kv_trips"] == 2 == sum(
        int(da.kv_walk_counts(*seen[d * k * layers], 640, 640)[1].sum())
        for d in range(2)
    )
    assert "mlcomp_engine_attention_kv_trips_total 2" in text


def test_no_dense_int8_walk_no_tokens_fetched():
    """A bfloat16 cache is not walked by the int8 kernel: the counters
    stay 0 and the share says nothing."""
    model, params = _build({**DENSE, "kv_quant": False})
    eng = _engine(model, params)
    try:
        assert eng._kv_walk is None
        eng.submit([3, 14, 15], 4).result(timeout=300)
        att = eng.stats()["attention"]
    finally:
        eng.close()
    assert att["kv_tokens_fetched"] == 0 == att["kv_trips"]
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

    assert (COUNTS.name, COUNTS.names) == ("retention", STATE_COUNTS)
    model, params = _build(RETENTION)
    k, layers = 2, 2
    eng = DecodeEngine(model, {"params": params}, slots=3,
                       prompt_buckets=(16,), max_new_cap=16,
                       steps_per_dispatch=k, prefill_chunk=8,
                       pipeline_depth=1)
    try:
        _, packed = jax.eval_shape(
            eng._dispatch_fn(), eng.variables, eng._dstate)
        assert packed.shape == (3 * k * 3 + len(COUNTS.entries),)
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


# ---- two more groups, in ONE stack: a delta-rule state's counts beside
# a latent cache's (models/kda.py, models/latent_attention.py) ----

STATES_AND_LATENTS = {
    "name": "mixed_layer_lm", "vocab_size": 64, "hidden": 64, "head_dim": 16,
    "kv_heads": 4, "layer_types": ["kda", "latent", "kda"],
    "heads_per_layer": [4, 4, 4], "mlp_layer_types": ["dense"] * 3,
    "mlp_dim": 128, "latent_dims": [16, 8, 16, 32], "dtype": "float32",
}


def test_the_state_and_the_latent_layers_counts_are_the_hand_counts():
    """One request alone on three slots: 12 prompt tokens in a bucket of
    16 are two chunks of 8 (four pads in the first), then three
    dispatches of K = 2 steps, the one live row through two KDA layers
    and one latent layer whose window grows a token a step."""
    from mlcomp_tpu.models import kda, latent_attention
    from mlcomp_tpu.ops.pallas.kda import state_bytes_moved

    assert (kda.COUNTS.name, kda.COUNTS.names) == ("kda", STATE_COUNTS)
    assert latent_attention.COUNTS.name == "latent"
    assert latent_attention.COUNTS.names == (
        "tokens_attended", "bytes_read", "chunk_tokens", "layer_calls")
    model, params = _build(STATES_AND_LATENTS)
    k, n_kda = 2, 2
    eng = DecodeEngine(model, {"params": params}, slots=3,
                       prompt_buckets=(16,), max_new_cap=16,
                       steps_per_dispatch=k, prefill_chunk=8,
                       pipeline_depth=1)
    try:
        _, packed = jax.eval_shape(
            eng._dispatch_fn(), eng.variables, eng._dstate)
        assert packed.shape == (3 * k * 3 + 4 + 4,)
        assert eng._count_layers == {"kda": 2, "latent": 1}
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
    steps = 3 * k
    assert "moe" not in st and "retention" not in st
    assert st["kda"] == {
        "state_rows": steps * n_kda,
        "state_bytes": state_bytes_moved(steps * n_kda, 4, 16, 16),
        "chunk_tokens": 12 * n_kda,
        "layer_calls": (2 + steps) * n_kda,
        "state_rows_over_issued": 1.0,
    }
    # step j attends the 12 prompt tokens and its own j + 1; the buffer
    # of 33 slots is one block of 48, in a leaf 128 lanes wide
    attended = sum(12 + j + 1 for j in range(steps))
    fetched = steps * 48 * 128 * 4
    assert st["latent"] == {
        "tokens_attended": attended, "bytes_read": fetched,
        "chunk_tokens": 12, "layer_calls": 2 + steps,
        "tokens_per_fetched_kb": round(attended / (fetched / 1024), 4),
    }
    # the engine's own count of context tokens: the one layer that
    # reads them, the whole context
    assert st["attention"]["kv_tokens_attended_share"] == 1.0
    for line in (f"mlcomp_engine_kda_state_rows_total {steps * n_kda}",
                 f"mlcomp_engine_kda_chunk_tokens_total {12 * n_kda}",
                 f"mlcomp_engine_latent_tokens_attended_total {attended}",
                 f"mlcomp_engine_latent_bytes_read_total {fetched}"):
        assert line in text
    assert "mlcomp_engine_retention_" not in text


def test_a_cache_with_a_state_refuses_pages_and_prefixes_by_the_leaf():
    """The latent leaf has a token axis (``SLOT_AXES``), the state and
    the convolution's tail have none: whatever moves KV by pages or by
    prefix is refused by those leaves' names, as for retention."""
    from mlcomp_tpu.cache.kv_store import HEAD_AXES, SLOT_AXES

    assert SLOT_AXES["cached_latent"] == 1 and "cached_latent" not in HEAD_AXES
    model, params = _build(STATES_AND_LATENTS)
    with pytest.raises(ValueError, match=r"slot state \['conv', 'state'\]"):
        DecodeEngine(model, {"params": params}, slots=2,
                     prompt_buckets=(16,), max_new_cap=16,
                     steps_per_dispatch=2, prefill_chunk=8,
                     kv_layout="paged", kv_page_tokens=8)


# ---- the admission lane's books -------------------------------------

PLAIN = {"name": "transformer_lm", "vocab_size": 64, "hidden": 32,
         "layers": 1, "heads": 2, "mlp_dim": 64, "dtype": "float32"}
ONE_CHUNK = [5, 6, 7]              # 3 tokens left-padded to 32: 1 chunk of 8
THREE_CHUNKS = list(range(1, 21))  # 20 tokens: 3 chunks, one all-pad skipped


def _lane_engine(slots, **kw):
    model, params = _build(PLAIN)
    return DecodeEngine(model, {"params": params}, slots=slots,
                        prompt_buckets=(32,), max_new_cap=64,
                        steps_per_dispatch=2, prefill_chunk=8, **kw)


def _decoding(eng, ids, n_new):
    """Submit and wait for the first token: the row is on the carry."""
    import queue

    stream: "queue.Queue" = queue.Queue()
    fut = eng.submit(ids, n_new, stream=stream)
    assert stream.get(timeout=300) is not None
    return fut


def _req_events(eng, name):
    return {int(e["id"]): e for e in eng.recorder.events
            if e.get("cat") == "req" and e["name"] == name}


def _track(eng, track, ph="X"):
    tid = eng.recorder._tracks.get(track)
    return sorted((e for e in eng.recorder.events
                   if e["tid"] == tid and e["ph"] == ph),
                  key=lambda e: e["ts"])


@pytest.fixture(scope="module")
def lane_run():
    """Four slots: A decodes in one, B's three-chunk admission holds the
    lane with C queued behind it, and nobody asks for the fourth.  Every
    dispatch sleeps 50 ms while B and C are submitted, so one pump parks
    both.  ``_issue`` is wrapped to keep, dispatch by dispatch, the
    mirror it saw and what it booked."""
    from mlcomp_tpu.utils import faults

    eng = _lane_engine(4)
    issued = []
    real = eng._issue

    def spy(seq, fused):
        live = sum(s is not None for s in eng._host)
        waiting = len(eng._pending) + (eng._adm is not None)
        before = dict(eng._pstats)
        real(seq, fused)
        issued.append((live, waiting) + tuple(
            eng._pstats[k] - before[k]
            for k in ("rows_attended", "rows_starved", "rows_total")))

    try:
        idle = eng.stats()["admission"]
        fa = _decoding(eng, ONE_CHUNK, 60)
        alone = eng.stats()["admission"]
        eng._issue = spy
        faults.arm("engine.dispatch", flavor="sleep", times=-1, seconds=0.05)
        fb = eng.submit(THREE_CHUNKS, 4)
        fc = eng.submit(ONE_CHUNK, 4)
        fb.result(timeout=300), fc.result(timeout=300)
        faults.disarm_all()
        fa.result(timeout=300)
        yield {"issued": issued, "stats": eng.stats(), "eng": eng,
               "idle": idle, "alone": alone,
               "rids": (fa.rid, fb.rid, fc.rid),
               "text": eng.metrics.render()}
    finally:
        faults.disarm_all()
        eng.close()


def test_every_dispatch_splits_its_rows_three_ways(lane_run):
    issued, att = lane_run["issued"], lane_run["stats"]["attention"]
    assert issued
    for live, waiting, attended, starved, total in issued:
        assert total == 4 and attended == live
        assert starved == min(total - live, waiting)
        assert attended + starved <= total   # the rest nobody asked for
    # A's row attended, B's (mid-prefill) and C's (queued) starved, one
    # row nobody asked for: the split of B's fused chunk dispatches
    assert (1, 2, 1, 2, 4) in issued
    # once everyone is in, the last row is merely unasked
    assert (3, 0, 3, 0, 4) in issued
    assert att["rows_starved"] >= sum(row[3] for row in issued) > 0
    assert att["rows_attended"] + att["rows_starved"] <= att["rows_total"]
    assert (f"mlcomp_engine_attention_rows_starved_total "
            f"{att['rows_starved']}") in lane_run["text"]


def test_the_head_of_line_books_say_what_the_queue_waited_for(lane_run):
    zero = {"lane": 0.0, "slot": 0.0, "pages": 0.0}
    # an idle engine books nothing, and neither does a request alone
    assert lane_run["idle"] == {"lane_busy_ms": 0.0, "admissions": 0,
                                "boundaries": 0, "blocked_ms": zero}
    assert lane_run["alone"]["blocked_ms"] == zero
    assert lane_run["alone"]["admissions"] == 1
    adm = lane_run["stats"]["admission"]
    # C sat behind B's admission with rows free: the lane, not a slot
    assert adm["blocked_ms"]["lane"] >= 100.0   # two boundaries of 50 ms
    assert adm["blocked_ms"]["slot"] == adm["blocked_ms"]["pages"] == 0.0
    assert adm["admissions"] == 3 and adm["boundaries"] == 1 + 3 + 1
    _, rid_b, rid_c = lane_run["rids"]
    admit = _req_events(lane_run["eng"], "admit")
    assert admit[rid_b]["args"]["blocked_ms"] == zero
    assert admit[rid_c]["args"]["blocked_ms"]["lane"] == pytest.approx(
        adm["blocked_ms"]["lane"], abs=0.01)
    text = lane_run["text"]
    assert 'mlcomp_engine_admission_blocked_ms_total{reason="lane"}' in text
    assert "mlcomp_engine_admission_lane_busy_ms_total" in text


def test_an_admits_blocked_ms_sums_to_its_queued_ms_within_a_boundary(
        lane_run):
    eng = lane_run["eng"]
    longest = max(e["dur"] for e in _track(eng, "engine.loop")
                  if e["name"] == "boundary") / 1e3
    admits = _req_events(eng, "admit")
    assert len(admits) == 3
    for ev in admits.values():
        booked = sum(ev["args"]["blocked_ms"].values())
        assert -0.01 <= ev["args"]["queued_ms"] - booked <= longest
    assert admits[lane_run["rids"][2]]["args"]["queued_ms"] >= 100.0


def test_the_lane_track_is_the_lane(lane_run):
    eng, adm = lane_run["eng"], lane_run["stats"]["admission"]
    spans = _track(eng, "engine.lane")
    assert [s["name"] for s in spans] == ["admission"] * 3
    for a, b in zip(spans, spans[1:]):
        assert a["ts"] + a["dur"] <= b["ts"]      # one lane: no overlap
    # the same stamps close the counter and the spans
    assert adm["lane_busy_ms"] == pytest.approx(
        sum(s["dur"] for s in spans) / 1e3, abs=0.01)
    inserted = _req_events(eng, "inserted")
    for s in spans:
        a = s["args"]
        assert a["caused_by"] == str(a["rid"]) and a["bucket"] == 32
        ins = inserted[a["rid"]]["args"]
        assert (a["chunks"], a["fused_chunks"], a["boundaries"]) == (
            ins["chunks"], ins["fused_chunks"], ins["boundaries"])
    by_rid = {s["args"]["rid"]: s["args"] for s in spans}
    rid_a, rid_b, rid_c = lane_run["rids"]
    assert by_rid[rid_a]["fused_chunks"] == 0      # nothing to ride
    assert (by_rid[rid_b]["chunks"], by_rid[rid_b]["fused_chunks"],
            by_rid[rid_b]["boundaries"]) == (3, 3, 3)
    assert by_rid[rid_c]["boundaries"] == 1


def test_a_compile_instant_lies_in_the_span_that_paid_for_it(lane_run):
    eng = lane_run["eng"]
    compiles = _track(eng, "engine.compile", ph="i")
    assert compiles and all(c["name"] == "compile" and
                            c["args"]["seconds"] >= 0 for c in compiles)
    paid = [s for s in _track(eng, "engine.loop") if s["name"] in (
        "issue", "prefill_chunk", "insert", "admission_start")]
    inside = [c for c in compiles if any(
        s["ts"] <= c["ts"] <= s["ts"] + s["dur"] for s in paid)]
    # the first staged chunk, the insert, the plain and the fused
    # dispatch programs were all first used on the loop
    assert len(inside) >= 4
    programs = lane_run["stats"]["programs"]
    assert programs["compiled"] >= len(compiles)
    assert programs["compile_seconds"] >= sum(
        c["args"]["seconds"] for c in compiles) - 1e-3
    assert "mlcomp_engine_programs_compiled_total" in lane_run["text"]
    assert "mlcomp_engine_programs_compile_seconds_total" in lane_run["text"]


@pytest.mark.parametrize("fused", [True, False])
def test_inserted_boundaries_is_the_hand_count(fused):
    """A prompt of one chunk is admitted and inserted inside one loop
    iteration; one of three chunks runs a chunk an iteration, fused onto
    A's dispatches or staged between them."""
    eng = _lane_engine(3, fused_admission=fused)
    try:
        _decoding(eng, ONE_CHUNK, 60)
        one = eng.submit(ONE_CHUNK, 2)
        one.result(timeout=300)
        three = eng.submit(THREE_CHUNKS, 2)
        three.result(timeout=300)
        inserted = _req_events(eng, "inserted")
    finally:
        eng.close()
    for fut, chunks in ((one, 1), (three, 3)):
        assert inserted[fut.rid]["args"] == {
            "chunks": chunks, "fused_chunks": chunks if fused else 0,
            "boundaries": chunks, "of": 4}


def test_blocked_on_a_slot_and_a_cancelled_admission_closes_its_span():
    """Both slots taken: B queues for a slot, not for the lane.  When
    the short request retires, B's chunks ride the long one's dispatches
    (100 ms each), and B is cancelled between them: its span on the
    lane's track closes with ``error``, and C's follows it without
    overlap."""
    import time

    from mlcomp_tpu.engine import RequestCancelled
    from mlcomp_tpu.utils import faults

    eng = _lane_engine(2)
    try:
        fa = _decoding(eng, ONE_CHUNK, 60)
        # armed before the short request starts: five of its dispatches
        # are still to come when its first token lands, however late a
        # loaded host lets this thread go on
        faults.arm("engine.dispatch", flavor="sleep", times=-1, seconds=0.1)
        _decoding(eng, ONE_CHUNK, 12)
        fb = eng.submit(THREE_CHUNKS, 4)
        for _ in range(3000):
            adm = eng._adm
            if adm is not None and adm.req["rid"] == fb.rid:
                break
            time.sleep(0.01)
        assert eng.cancel(fb.rid)
        with pytest.raises(RequestCancelled):
            fb.result(timeout=60)
        faults.disarm_all()
        eng.submit(ONE_CHUNK, 2).result(timeout=300)
        fa.result(timeout=300)
        st = eng.stats()["admission"]
        spans = _track(eng, "engine.lane")
        admit = _req_events(eng, "admit")
    finally:
        faults.disarm_all()
        eng.close()
    assert st["blocked_ms"]["slot"] >= 100.0
    assert st["blocked_ms"]["lane"] == st["blocked_ms"]["pages"] == 0.0
    assert admit[fb.rid]["args"]["blocked_ms"]["slot"] == pytest.approx(
        st["blocked_ms"]["slot"], abs=0.01)
    assert [bool(s["args"].get("error")) for s in spans] == [
        False, False, True, False]
    assert spans[2]["args"]["rid"] == fb.rid
    assert spans[2]["args"]["chunks"] < 3
    for a, b in zip(spans, spans[1:]):
        assert a["ts"] + a["dur"] <= b["ts"]
    assert st["admissions"] == 4


def test_an_admission_that_fails_as_it_starts_closes_its_span():
    """``_start_admission`` raises after the ``admit`` (the fresh prefill
    cache cannot be made): the request fails, and the lane's track and
    books hold the admission all the same, closed with ``error``; the
    next request finds the lane free."""
    eng = _lane_engine(2)
    real = eng._prefill_init_fn

    def broken():
        eng._prefill_init_fn = real
        raise RuntimeError("no prefill cache")

    try:
        eng._prefill_init_fn = broken
        bad = eng.submit(ONE_CHUNK, 2)
        with pytest.raises(RuntimeError, match="no prefill cache"):
            bad.result(timeout=300)
        good = eng.submit(ONE_CHUNK, 2)
        good.result(timeout=300)
        st = eng.stats()["admission"]
        spans = _track(eng, "engine.lane")
        admit = _req_events(eng, "admit")
    finally:
        eng.close()
    assert sorted(admit) == [bad.rid, good.rid]
    assert [(s["args"]["rid"], bool(s["args"].get("error")))
            for s in spans] == [(bad.rid, True), (good.rid, False)]
    assert spans[0]["ts"] + spans[0]["dur"] <= spans[1]["ts"]
    assert st["admissions"] == 2 and eng._in_lane is None


# ---- a conv layer's group, beside int8 keys and values in one carry
# (models/short_conv.py) ----

TAILS_AND_KEYS = {
    "name": "mixed_layer_lm", "vocab_size": 64, "hidden": 64, "head_dim": 16,
    "kv_heads": 2, "layer_types": ["conv", "full", "conv"],
    "heads_per_layer": [4, 4, 4], "mlp_layer_types": ["dense"] * 3,
    "mlp_dim": 128, "conv_taps": 3, "qk_norm": True, "kv_quant": True,
    "rope_full": {"base": 1000000.0}, "dtype": "float32",
}


def test_a_conv_layers_counts_are_the_hand_counts():
    """One request alone on three slots: 12 prompt tokens in a bucket of
    16 are two chunks of 8 (four pads in the first), then three
    dispatches of K = 2 steps, the one live row through two conv layers
    and one attention layer, whose context tokens are counted for that
    layer alone."""
    from mlcomp_tpu.models.short_conv import COUNTS

    assert (COUNTS.name, COUNTS.names) == ("conv", STATE_COUNTS)
    model, params = _build(TAILS_AND_KEYS)
    assert model.attention_windows() == (None,)
    k, n_conv = 2, 2
    eng = DecodeEngine(model, {"params": params}, slots=3,
                       prompt_buckets=(16,), max_new_cap=16,
                       steps_per_dispatch=k, prefill_chunk=8,
                       pipeline_depth=1)
    try:
        _, packed = jax.eval_shape(
            eng._dispatch_fn(), eng.variables, eng._dstate)
        assert packed.shape == (3 * k * 3 + len(COUNTS.entries),)
        assert eng._count_layers == {"conv": 2}
        out = eng.submit(list(range(1, 13)), 3 * k).result(timeout=300)
        st = eng.stats()
        text = eng.metrics.render()
    finally:
        eng.close()
    assert len(out["ids"]) == 3 * k and st["pipeline"]["issued"] == 3
    steps = 3 * k
    # a tail is two tokens of 64 channels, float32 here, read and written
    a_tail = 2 * 2 * 64 * 4
    assert "moe" not in st and "kda" not in st
    assert st["conv"] == {
        "state_rows": steps * n_conv,
        "state_bytes": steps * n_conv * a_tail,
        "chunk_tokens": 12 * n_conv,
        "layer_calls": (2 + steps) * n_conv,
        "state_rows_over_issued": 1.0,
    }
    # the engine's own count of context tokens: the ONE layer that
    # reads them (12, 14 and 16 tokens at the three issues), not three
    att = st["attention"]
    assert att["kv_tokens_live"] == att["kv_tokens_attended"] == 12 + 14 + 16
    assert att["kv_tokens_fetched"] >= att["kv_tokens_attended"]
    for line in (f"mlcomp_engine_conv_state_rows_total {steps * n_conv}",
                 f"mlcomp_engine_conv_state_bytes_total "
                 f"{steps * n_conv * a_tail}",
                 f"mlcomp_engine_conv_chunk_tokens_total {12 * n_conv}",
                 f"mlcomp_engine_conv_layer_calls_total "
                 f"{(2 + steps) * n_conv}"):
        assert line in text
    assert "mlcomp_engine_kda_" not in text


def test_a_conv_tail_refuses_pages_and_prefixes_by_the_leaf():
    """The int8 keys and values have a token axis, the tail beside them
    has none: whatever moves KV by pages or by prefix is refused by the
    tail's name, though the attention layer alone could be paged."""
    model, params = _build(TAILS_AND_KEYS)
    with pytest.raises(ValueError, match=r"slot state \['conv'\]"):
        DecodeEngine(model, {"params": params}, slots=2,
                     prompt_buckets=(16,), max_new_cap=16,
                     steps_per_dispatch=2, prefill_chunk=8,
                     kv_layout="paged", kv_page_tokens=8)


# ---- a layer kind costs the engine no line: its table is its own ----

def _toy_lm(group):
    """A decoder whose model call sows a vector of its own under
    ``group``: the positions it was handed, and 1 (the call)."""
    import flax.linen as nn

    from mlcomp_tpu.models.transformer import TransformerLM

    class ToyLM(TransformerLM):
        @nn.compact
        def __call__(self, x, *args, decode=False, **kw):
            out = super().__call__(x, *args, decode=decode, **kw)
            if decode:
                self.sow(
                    "counters", group,
                    jnp.asarray([x.size, 1.0], jnp.float32),
                    reduce_fn=lambda a, c: a + c,
                    init_fn=lambda: jnp.zeros((2,), jnp.float32),
                )
            return out

    model = ToyLM(vocab_size=64, hidden=64, layers=1, heads=2, mlp_dim=128,
                  dtype=jnp.float32)
    prompt = jnp.asarray(np.random.RandomState(0).randint(1, 64, (1, 8)))
    params, _ = init_model(model, {"x": prompt}, jax.random.PRNGKey(0))
    return model, params


def _toy_engine(model, params):
    return DecodeEngine(model, {"params": params}, slots=3,
                        prompt_buckets=(16,), max_new_cap=16,
                        steps_per_dispatch=2, prefill_chunk=8,
                        pipeline_depth=1)


@pytest.mark.parametrize("block", ["sums", "share"])
def test_a_layer_kind_declared_beside_its_layer_is_served_and_counted(block):
    """The same traffic as the state kinds' hand counts (two chunks of
    8, three dispatches of 2 steps on three slots), through a layer
    kind this file alone knows: ``stats()`` has its block (the sums, or
    what the table's function makes of them) and ``/metrics`` its
    counters, help texts and all."""
    from mlcomp_tpu.models.counts import count_group

    group = f"toy_{block}".lower()
    table = count_group(group, (
        ("positions", "Positions the toy model's calls were handed"),
        ("layer_calls", "Toy-model calls (steps, and chunks)"),
    ), **({} if block == "sums" else {"block": lambda sums, issued: {
        **sums, "issued": issued,
        "positions_per_call": sums["positions"] / sums["layer_calls"],
    }}))
    model, params = _toy_lm(group)
    eng = _toy_engine(model, params)
    try:
        assert eng._count_layers == {group: 1}
        _, packed = jax.eval_shape(
            eng._dispatch_fn(), eng.variables, eng._dstate)
        assert packed.shape == (3 * 2 * 3 + len(table.entries),)
        out = eng.submit(list(range(1, 13)), 6).result(timeout=300)
        st = eng.stats()
        text = eng.metrics.render()
    finally:
        eng.close()
    assert len(out["ids"]) == 6 and st["pipeline"]["issued"] == 3
    sums = {"positions": 2 * 8 + 6 * 3, "layer_calls": 2 + 6}
    if block == "sums":
        assert st[group] == sums
    else:
        # one live row, six steps, one "layer": what the host issued
        assert st[group] == {**sums, "issued": 6, "positions_per_call": 4.25}
    assert f"mlcomp_engine_{group}_positions_total 34" in text
    assert f"mlcomp_engine_{group}_layer_calls_total 8" in text
    assert (f"# HELP mlcomp_engine_{group}_positions_total Positions the "
            "toy model's calls were handed") in text


def test_a_layer_kind_without_a_table_is_refused_by_name():
    model, params = _toy_lm("toy_undeclared")
    with pytest.raises(ValueError, match=r"\['toy_undeclared'\].*count_group"):
        _toy_engine(model, params)


def test_a_groups_name_is_declared_once():
    """Declaring the same table again (a module imported twice) is the
    same table in the same place; other entries under a taken name are
    another layer kind's, and refused."""
    from mlcomp_tpu.models.counts import count_group, count_groups
    from mlcomp_tpu.models.short_conv import COUNTS

    before = [g.name for g in count_groups(["moe", "conv"])]
    again = count_group(COUNTS.name, COUNTS.entries, block=COUNTS.block)
    assert again == COUNTS
    assert [g.name for g in count_groups(["moe", "conv"])] == before
    with pytest.raises(ValueError, match="already declared"):
        count_group("conv", (("rows", "Rows"),))


# two shortcut layers of latent attention: a router over 8 real experts
# (4 held) and 4 zero-compute ones, the weights not renormalised
SHORTCUT = {
    "name": "mixed_layer_lm", "vocab_size": 64, "hidden": 128, "head_dim": 16,
    "kv_heads": 4, "layer_types": ["latent", "latent"],
    "heads_per_layer": [4, 4], "mlp_layer_types": ["shortcut"] * 2,
    "mlp_dim": 128, "experts": 8, "zero_experts": 4, "experts_per_token": 3,
    "experts_held": [0, 4], "routed_scale": 6.0, "renormalise": False,
    "expert_width": 128, "selection_bias": True,
    "rope_full": {"base": 1e7}, "latent_dims": [16, 8, 16, 32],
    "latent_q_rank": 24, "latent_lora_scales": [True, True],
    "dtype": "float32",
}


def test_the_zero_experts_choices_are_counted_through_stats_and_metrics():
    """One request alone on three slots, 12 prompt tokens in two chunks
    of 8 then three dispatches of K = 2: ``zero_assignments`` and its
    chunk twin close the expert layers' vector, reach
    ``stats()["engine"]["moe"]`` whole and by call class, and
    ``/metrics`` under their own names; ``assignments`` stays all three
    choices a token; and a shortcut layer's two mixers each count as a
    layer that reads the context."""
    model, params = _build(SHORTCUT)
    assert model.attention_windows() == (None,) * 4
    k, layers, top_k, slots = 2, 2, 3, 3
    eng = DecodeEngine(model, {"params": params}, slots=slots,
                       prompt_buckets=(16,), max_new_cap=16,
                       steps_per_dispatch=k, prefill_chunk=8,
                       pipeline_depth=1)
    try:
        _, packed = jax.eval_shape(
            eng._dispatch_fn(), eng.variables, eng._dstate)
        from mlcomp_tpu.models.latent_attention import COUNTS as LATENT

        assert packed.shape == (3 * k * slots + len(MOE_COUNTS.entries)
                                + len(LATENT.entries),)
        out = eng.submit(list(range(1, 13)), 3 * k).result(timeout=300)
        st = eng.stats()
        text = eng.metrics.render()
    finally:
        eng.close()
    assert len(out["ids"]) == 3 * k
    moe = st["moe"]
    chunk_calls, step_calls = 2 * layers, 3 * k * layers
    chunk, single = moe["by_class"]["chunk"], moe["by_class"]["single_token"]
    assert chunk["assignments"] == 8 * top_k * chunk_calls
    assert single["assignments"] == slots * top_k * step_calls
    # 4 of 12 outputs are zero experts: some choices, not all
    for c in (chunk, single):
        assert 0 < c["zero_assignments"] < c["assignments"]
        # a zero choice is no held assignment
        assert c["assignments_held"] + c["zero_assignments"] \
            <= c["assignments"]
    assert moe["zero_assignments"] == chunk["zero_assignments"] \
        + single["zero_assignments"]
    for name, n in (("zero_assignments", moe["zero_assignments"]),
                    ("chunk_zero_assignments", chunk["zero_assignments"])):
        assert f"mlcomp_engine_moe_{name}_total {int(n)}" in text
    assert ("# HELP mlcomp_engine_moe_zero_assignments_total Assignments "
            "to zero-compute (identity) experts") in text
    # both mixers of both layers read the context: 12 + 14 + 16 held
    att = st["attention"]
    assert att["kv_tokens_live"] == att["kv_tokens_attended"] \
        == 4 * (12 + 14 + 16)
    lat = st["latent"]
    assert lat["layer_calls"] == 2 * (chunk_calls + step_calls)
    assert lat["chunk_tokens"] == 4 * 12
