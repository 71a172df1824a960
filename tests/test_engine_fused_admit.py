"""Fused prefill+decode dispatch (engine ``fused_admission``, default
on): an admission's chunks ride the decode dispatches instead of
running as lone dispatches at drained boundaries.  The acceptance
contract: decode rows AND the admitted request's tokens are
bit-identical between the fused and staged paths — on both cache
layouts, across pipeline depths, through a prefix-cache hit landing
mid-admission, and with EOS retiring a neighbour mid-prefill — and a
fault inside the fused prep fails ONLY the admitting request."""

import functools
import queue
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlcomp_tpu.engine import DecodeEngine
from mlcomp_tpu.models import create_model
from mlcomp_tpu.models.generation import generate
from mlcomp_tpu.serve import GenerationService
from mlcomp_tpu.train.state import init_model
from mlcomp_tpu.utils import faults
from test_engine_counters import MIXED


@functools.lru_cache(maxsize=None)
def _model_and_params(kv_quant=False, seed=0):
    # cached across tests: init is deterministic per (kv_quant, seed)
    # and nothing mutates the returned pytree
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


IDS_A = [3, 14, 15, 9, 2]
IDS_B = [7, 3, 44, 5, 6]

# compiled-program cache across same-config engines: fused/staged/pipeline-depth are host-side knobs, so
# every engine a workload key builds runs the identical program set —
# compile once per key instead of once per engine
_FNS: dict = {}


def _share_fns(eng, key):
    eng._fns.update(_FNS.setdefault(key, {}))
    return eng


N_A = 44  # 22 dispatches of K=2: B's two chunks find A decoding


def _overlapped_workload(model, params, fused, depth=2, prefill_chunk=4,
                         fns_key=None):
    """A decodes while B's multi-chunk admission runs — with
    prefill_chunk=4 in the 16 bucket, B (5 real tokens, start pad 11)
    runs chunks 2 and 3, overlapped with A's decode.  B is submitted
    right behind A, not after this thread has seen A's first token:
    the loop admits B once A is inserted, with all of A's 22
    dispatches ahead, however late a loaded machine schedules this
    thread.  Returns the comparable outputs plus the engine stats,
    with the flight recorder's own account of B's chunks."""
    eng = DecodeEngine(model, {"params": params}, slots=2,
                       prompt_buckets=(16,), max_new_cap=48,
                       steps_per_dispatch=2, pipeline_depth=depth,
                       prefill_chunk=prefill_chunk,
                       fused_admission=fused)
    if fns_key is not None:
        _share_fns(eng, fns_key)
    try:
        fa = eng.submit(IDS_A, N_A, logprobs=True)
        fb = eng.submit(IDS_B, 6, logprobs=True)
        ra = fa.result(timeout=300)
        rb = fb.result(timeout=300)
        st = eng.stats()
        # B's ``prefill_chunk`` spans, and those of them that rode a
        # decode dispatch (``fused``)
        chunks_b = [
            e for e in eng.recorder.events
            if e["name"] == "prefill_chunk" and e.get("ph") == "X"
            and e["args"]["rid"] == fb.rid
        ]
        st["recorded_chunks_b"] = len(chunks_b)
        st["recorded_fused_chunks_b"] = sum(
            bool(e["args"].get("fused")) for e in chunks_b
        )
    finally:
        if fns_key is not None:
            _FNS[fns_key].update(eng._fns)
        eng.close()
    return {"a": (ra["ids"], ra["logprobs"]),
            "b": (rb["ids"], rb["logprobs"])}, st


@pytest.mark.parametrize("kv_quant", [False, True])
def test_fused_bit_identical_to_staged(kv_quant):
    """The acceptance equality: with B's admission overlapping A's
    decode, fused and staged engines emit bit-identical tokens AND
    logprobs for both the decode rows and the admitted request (its
    first token comes from the fused program's chunk half), on both
    cache layouts — and both match bare generate."""
    model, params = _model_and_params(kv_quant)
    key = ("overlap", kv_quant)
    fused, st_f = _overlapped_workload(model, params, True, fns_key=key)
    staged, st_s = _overlapped_workload(model, params, False, fns_key=key)
    assert fused == staged
    assert fused["a"][0] == _reference(model, params, IDS_A, N_A)
    assert fused["b"][0] == _reference(model, params, IDS_B, 6)
    # counter contract: a fused chunk counts exactly like a staged one
    # (no double count), and the overlapped admission is recorded
    assert st_f["prefill_chunks"] == st_s["prefill_chunks"]
    assert st_f["prefills"] == st_s["prefills"] == 2
    # the counters are held to the loop's own record of B's chunks
    # (A is admitted onto an idle engine: its chunks never ride): B ran
    # two, the fused engine's counters say how many of them the
    # recorder saw riding a dispatch, and an admission counts as
    # overlapped exactly when one did
    assert st_f["recorded_chunks_b"] == st_s["recorded_chunks_b"] == 2
    rode = st_f["recorded_fused_chunks_b"]
    assert st_f["fused_chunks"] == rode
    assert st_f["admissions_overlapped"] == (1 if rode else 0)
    assert rode >= 1     # B joined with >= 20 of A's dispatches to go
    assert st_s["recorded_fused_chunks_b"] == 0
    assert st_s["fused_chunks"] == 0
    assert st_s["admissions_overlapped"] == 0
    assert st_f["fused_admission"] is True
    assert st_s["fused_admission"] is False


def test_fused_depth1_vs_depth2():
    """The fused path composes with the dispatch pipeline: depth 1 and
    depth 2 emit identical outputs with an admission in flight."""
    model, params = _model_and_params()
    key = ("overlap", False)
    d1, _ = _overlapped_workload(model, params, True, depth=1, fns_key=key)
    d2, _ = _overlapped_workload(model, params, True, depth=2, fns_key=key)
    assert d1 == d2


def test_prefix_cache_hit_mid_admission_fused():
    """A prefix-cache hit landing mid-admission keeps its
    chunk-skipping semantics on the fused path: the suffix chunk rides
    a decode dispatch, tokens stay exact vs the cold run and vs the
    staged engine, and hit accounting is identical."""
    from mlcomp_tpu.cache import PrefixKVCache

    model, params = _model_and_params()
    shared = [9, 10, 11, 12, 13, 14, 15, 16, 17]   # 9 real tokens
    results = {}
    for fused in (True, False):
        cache = PrefixKVCache(max_bytes=1 << 22)
        eng = _share_fns(
            DecodeEngine(model, {"params": params}, slots=2,
                         prompt_buckets=(16,), max_new_cap=12,
                         steps_per_dispatch=2, prefill_chunk=4,
                         prefix_cache=cache, fused_admission=fused),
            ("workload", False),   # same program set as the workload
        )
        try:
            cold = eng.submit(shared, 6).result(timeout=300)
            cache.flush()                 # capture lands in the trie
            qa: "queue.Queue" = queue.Queue()
            fa = eng.submit(IDS_A, 10, stream=qa)
            qa.get(timeout=300)           # A is decoding
            hit = eng.submit(shared, 6).result(timeout=300)
            ra = fa.result(timeout=300)
            st = eng.stats()
        finally:
            _FNS[("workload", False)].update(eng._fns)
            eng.close()
        assert cold["cache_hit_tokens"] == 0
        # 9 tokens, start pad 7, chunk 4: hit covers through chunk 2's
        # boundary (12 slots) -> 5 prompt tokens skip their prefill
        assert hit["cache_hit_tokens"] == 5, hit
        assert hit["ids"] == cold["ids"]
        results[fused] = (cold["ids"], hit["ids"], ra["ids"], st["prefills"])
    assert results[True] == results[False]
    assert results[True][0] == _reference(model, params, shared, 6)


def test_eos_during_overlapped_admission():
    """A hits EOS while B's fused admission is mid-flight: A's slot
    frees and its stream terminates correctly, B's insert still lands,
    and everything matches the staged path."""
    model, params = _model_and_params()
    # A (prompt IDS_B) stops at its first greedy token, after token 0,
    # that no earlier token equals: an EOS that is also token 0 would
    # fire before B is even submitted (IDS_A's greedy continuation
    # under the installed JAX is one repeated token, so it cannot be
    # the stopper).  Deterministic reference.
    ref_a = _reference(model, params, IDS_B, 12)
    n_eos = next(
        i for i in range(1, len(ref_a)) if ref_a[i] not in ref_a[:i]
    )
    eos_a = ref_a[n_eos]
    results = {}
    for fused in (True, False):
        eng = _share_fns(
            DecodeEngine(model, {"params": params}, slots=2,
                         prompt_buckets=(16,), max_new_cap=12,
                         steps_per_dispatch=1, prefill_chunk=2,
                         fused_admission=fused),
            ("eos", 1, 2),
        )
        try:
            qa: "queue.Queue" = queue.Queue()
            fa = eng.submit(IDS_B, 12, eos_id=eos_a, stream=qa)
            qa.get(timeout=300)           # A is decoding
            fb = eng.submit(IDS_A, 6)     # chunks of 2: a long prefill
            ra = fa.result(timeout=300)
            rb = fb.result(timeout=300)
        finally:
            _FNS[("eos", 1, 2)].update(eng._fns)
            eng.close()
        assert ra["ids"] == ref_a[:n_eos + 1], ra
        results[fused] = (ra["ids"], rb["ids"])
    assert results[True] == results[False]
    assert results[True][1] == _reference(model, params, IDS_A, 6)


def test_fused_prefill_fault_fails_only_the_admission():
    """The engine.fused_prefill chaos point (host-side prep, before the
    combined device call): the admitting request fails with the fault,
    the decode fleet's tokens stay bit-identical to a fault-free run,
    the engine stays healthy, and the next admission succeeds."""
    model, params = _model_and_params()
    # A decodes for 20 dispatches, so that B joins it on a loaded host too
    ref_a = _reference(model, params, IDS_A, 40)
    ref_b = _reference(model, params, IDS_B, 6)
    eng = _share_fns(
        DecodeEngine(model, {"params": params}, slots=2,
                     prompt_buckets=(16,), max_new_cap=48,
                     steps_per_dispatch=2, prefill_chunk=4),
        ("fault", False),   # a buffer of its own length: programs of its own
    )
    try:
        qa: "queue.Queue" = queue.Queue()
        fa = eng.submit(IDS_A, 40, stream=qa)
        qa.get(timeout=300)               # A is decoding
        faults.arm("engine.fused_prefill", flavor="raise", times=1)
        fb = eng.submit(IDS_B, 6)
        with pytest.raises(faults.FaultInjected):
            fb.result(timeout=300)
        # survivor exact, engine alive, no admission state leaked
        assert fa.result(timeout=300)["ids"] == ref_a
        assert eng.healthy
        assert eng._adm is None
        # the slot the failed admission never took is still usable
        rb = eng.submit(IDS_B, 6).result(timeout=300)
        assert rb["ids"] == ref_b
        st = eng.stats()
        assert st["prefills"] == 2        # A + the retry, not the fault
        assert st["active_slots"] == 0 or st["active_slots"] == 1
    finally:
        faults.disarm_all()
        eng.close()


def test_staged_flag_plumbing_and_metrics():
    """--engine-staged-admission plumbing: the service forwards
    engine_fused_admission, the engine reports the mode in stats(), and
    the new admission metrics (fused chunk / overlap counters + the
    stall histogram) are in the exposition."""
    model, params = _model_and_params()
    svc = GenerationService(
        model, {"params": params}, batch_sizes=(1, 2),
        prompt_buckets=(16,), max_new_buckets=(8,),
        engine_fused_admission=False,
    )
    try:
        assert svc.engine.fused_admission is False
        svc.generate([5, 6, 7], 4)
        assert svc.stats()["engine"]["fused_admission"] is False
        text = svc.metrics.render()
        for name in ("mlcomp_engine_fused_prefill_chunks_total",
                     "mlcomp_engine_admissions_overlapped_total",
                     "mlcomp_engine_admission_stall_ms_bucket"):
            assert name in text, name
    finally:
        svc.close()
    # default is fused; warmup precompiles the fused program family
    svc = GenerationService(
        model, {"params": params}, batch_sizes=(1, 2),
        prompt_buckets=(16,), max_new_buckets=(8,),
    )
    try:
        assert svc.engine.fused_admission is True
        # one chunk width x every ladder rung (the serve default is
        # adaptive K, so the fused family precompiles per rung)
        ladder = svc.engine.k_ladder
        assert svc.engine.warm_fused_fns() == len(ladder)
        for k in ladder:
            assert ("fused_dispatch", 16, k) in svc.engine._fns
    finally:
        svc.close()


# ---- a chunk's program multiplies the head for the one row it keeps ----

# vocabulary 96 and chunk 8 are widths nothing else in these models has,
# so a (…, 8, 96) tensor can only be a whole chunk's logits
_VOCAB, _CHUNK, _K = 96, 8, 2
_ONE_ROW_MODELS = {
    "transformer_lm": {
        "name": "transformer_lm", "vocab_size": _VOCAB, "hidden": 64,
        "layers": 2, "heads": 2, "mlp_dim": 128, "dtype": "float32",
    },
    # a window of the bucket: ``generate`` prefills it in one piece
    "mixed_layer_lm": {**MIXED, "vocab_size": _VOCAB, "window": 16},
}


@functools.lru_cache(maxsize=None)
def _one_row_model(name, kv_quant=False):
    model = create_model({**_ONE_ROW_MODELS[name], "kv_quant": kv_quant})
    prompt = jnp.asarray(np.random.RandomState(0).randint(1, _VOCAB, (1, 8)))
    params, _ = init_model(model, {"x": prompt}, jax.random.PRNGKey(0))
    return model, params


def _one_row_engine(name, kv_quant=False, **kw):
    model, params = _one_row_model(name, kv_quant)
    return DecodeEngine(model, {"params": params}, slots=3,
                        prompt_buckets=(16,), max_new_cap=48,
                        steps_per_dispatch=_K, prefill_chunk=_CHUNK, **kw)


def _tensor_shapes(lowered_text):
    """Every tensor type of a lowered (StableHLO) program, as tuples."""
    return {
        tuple(int(d) for d in m.group(1).split("x"))
        for m in re.finditer(r"tensor<((?:\d+x)*\d+)x[a-z]\w*>", lowered_text)
    }


@pytest.mark.parametrize("program", ["staged", "fused"])
@pytest.mark.parametrize("name", sorted(_ONE_ROW_MODELS))
def test_a_chunks_program_multiplies_the_head_for_one_row(name, program):
    """The structural witness: no tensor of either chunk program has the
    shape (…, chunk, vocab) — XLA does not narrow a whole chunk's logits
    to the row the program slices from them, so they must never be asked
    for — while the one row, (1, 1, vocab) → (1, vocab), is there."""
    eng = _one_row_engine(name)
    try:
        i32 = jnp.int32
        chunk = (jax.ShapeDtypeStruct((1, _CHUNK), i32),
                 jax.ShapeDtypeStruct((1, _CHUNK), i32),
                 jax.ShapeDtypeStruct((1, eng.l_buf), bool))
        adm = jax.eval_shape(eng._prefill_init_fn(),
                             jax.ShapeDtypeStruct((), i32))
        if program == "staged":
            lowered = eng._prefill_chunk_fn(_CHUNK).lower(
                eng.variables, adm, *chunk)
        else:
            lowered = eng._fused_dispatch_fn(_CHUNK, _K).lower(
                eng.variables, jax.eval_shape(eng._fresh_dstate), adm, *chunk)
        shapes = _tensor_shapes(lowered.as_text())
    finally:
        eng.close()
    whole = {s for s in shapes if s[-2:] == (_CHUNK, _VOCAB)}
    assert not whole, f"a whole chunk's logits are formed: {sorted(whole)}"
    assert (1, 1, _VOCAB) in shapes and (1, _VOCAB) in shapes
    # the witness can see: the chunk's hidden states are in the program
    assert any(s[-2] == _CHUNK for s in shapes if len(s) >= 2)


@pytest.mark.parametrize("fused", [True, False])
@pytest.mark.parametrize("name, kv_quant", [
    ("transformer_lm", False), ("transformer_lm", True),
    ("mixed_layer_lm", False), ("mixed_layer_lm", True)])
def test_a_multi_chunk_admissions_tokens_are_generates(name, kv_quant, fused):
    """The first token of an admission comes from the last chunk's one
    row of logits: B's two chunks (fused: riding A's dispatches) and
    A's own end in the tokens ``generate`` emits for the full prompt."""
    model, params = _one_row_model(name, kv_quant)
    eng = _share_fns(
        _one_row_engine(name, kv_quant=kv_quant, fused_admission=fused),
        ("one_row", name, kv_quant))
    ids_a = [3, 14, 15, 9, 2, 7, 7, 30, 2, 1]
    ids_b = [7, 3, 44, 5, 6, 21, 8, 8, 50, 13, 4]
    try:
        fa = eng.submit(ids_a, N_A)
        fb = eng.submit(ids_b, 6)
        ra, rb = fa.result(timeout=300), fb.result(timeout=300)
        st = eng.stats()
    finally:
        _FNS[("one_row", name, kv_quant)].update(eng._fns)
        eng.close()
    assert st["prefill_chunks"] == 4          # two chunks each
    assert (st["fused_chunks"] >= 1) is fused
    assert ra["ids"] == _reference(model, params, ids_a, N_A)
    assert rb["ids"] == _reference(model, params, ids_b, 6)
