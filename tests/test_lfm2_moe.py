"""``mixed_layer_lm`` as LFM2-24B-A2B's layers: gated short-convolution
layers (``models/short_conv.py``) beside grouped-query attention with a
q/k norm a head (``SelfAttention.qk_norm``) in ONE stack, a two-token
tail beside int8 keys and values in one slot's carry, and a sigmoid
router whose bias joins the choice alone; against the plain reference
``benchmark/reference/lfm2_moe.py`` at tiny widths on the CPU with
seeded weights.  A file of its own: the tier-1 command deals FILES to
its workers."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from benchmark import weights as W
from mlcomp_tpu.engine import DecodeEngine
from mlcomp_tpu.models import create_model
from mlcomp_tpu.models.generation import generate, init_cache

ROOT = Path(__file__).resolve().parents[1]
SEED = 7


def _lfm2(**over):
    with open(ROOT / "benchmark/configs/_rehearsal"
              / "lfm2-24b-a2b-serve.json") as f:
        cfg = json.load(f)
    arch = cells.architecture(cfg)
    model = {**cfg["model"], "dtype": "float32", "head_dtype": "float32",
             "kv_quant": False, **over}
    return arch, arch.dims_of(cfg), model


IDS = np.asarray(jax.random.randint(jax.random.PRNGKey(0), (1, 50), 1, 512))


def _reference_logits(arch, d, ids, kv_fn=None):
    key = W.seed_key(SEED)
    top = arch.top_weights(key, d, jnp.float32)
    x = arch.embed(jnp.asarray(ids), top["emb"])
    pos = jnp.broadcast_to(jnp.arange(ids.shape[1])[None], ids.shape)
    kw = {} if kv_fn is None else {"kv_fn": kv_fn}
    for i, kind in enumerate(arch.layer_kinds(d)):
        x = arch.layer(x, arch.layer_weights(key, i, d, jnp.float32, kind),
                       pos, d, kind, **kw)
    return np.asarray(arch.logits(x, top, d))


def _served_logits(model, params, ids, n_prompt, bucket=32, chunk=8, l_buf=65):
    """The engine's contract on one row: a LEFT-padded prompt in chunks
    (pads and tokens share a chunk), then single-token steps at a
    cursor; the logits of the real positions.  Two jitted programs, as
    the engine has two."""
    pad = bucket - n_prompt
    row = np.zeros((1, bucket), np.int32)
    row[0, pad:] = ids[0, :n_prompt]
    positions = np.maximum(np.arange(bucket) - pad, 0)[None].astype(np.int32)
    kv_mask = jnp.asarray((np.arange(l_buf) >= pad)[None])

    @jax.jit
    def call(cache, tokens, positions, cursor):
        logits, upd = model.apply(
            {"params": params, "cache": cache}, tokens, decode=True,
            positions=positions, kv_mask=kv_mask, cache_cursor=cursor,
            mutable=["cache", "counters"])
        return logits, upd["cache"]

    cache = init_cache(model, 1, l_buf)
    out = []
    for lo in range(0, bucket, chunk):
        lg, cache = call(cache, jnp.asarray(row[:, lo:lo + chunk]),
                         jnp.asarray(positions[:, lo:lo + chunk]), None)
        out.append(np.asarray(lg))
    out = [np.concatenate(out, 1)[:, pad:]]
    for t in range(n_prompt, ids.shape[1]):
        lg, cache = call(cache, jnp.asarray(ids[:, t:t + 1]),
                         jnp.full((1, 1), t, jnp.int32),
                         jnp.array([bucket + t - n_prompt], jnp.int32))
        out.append(np.asarray(lg))
    return np.concatenate(out, 1)


@pytest.fixture(scope="module")
def served():
    """(the full forward's logits, the served path's): computed once,
    held to several references below."""
    arch, d, kw = _lfm2()
    model = create_model(dict(kw))
    params = W.program_params(arch, SEED, d, jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)))
        return whole, _served_logits(model, params, IDS, n_prompt=21)


def test_a_stack_of_tails_and_int8_keys_is_assembled_from_the_lists():
    arch, d, kw = _lfm2(kv_quant=True)
    assert arch.layer_kinds(d) == ["conv_dense", "attn_sparse", "conv_sparse"]
    model = create_model(dict(kw))
    # one layer reads context tokens, all of them; two read a tail
    assert model.attention_windows() == (None,)
    params = W.program_params(arch, SEED, d, jnp.float32)
    abstract = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    W.check_layout(params, abstract)
    assert set(params["layer_0"]) == {"attn", "RMSNorm_0", "gate", "up",
                                      "down"}
    assert set(params["layer_0"]["attn"]) == {"RMSNorm_0", "in", "conv", "out"}
    assert params["layer_0"]["attn"]["in"]["kernel"].shape == (256, 768)
    assert params["layer_0"]["attn"]["conv"].shape == (3, 256)
    assert set(params["layer_1"]["attn"]) == {
        "RMSNorm_0", "q", "k", "v", "q_norm", "k_norm", "out"}
    assert set(params["layer_1"]["moe"]) == {
        "router", "router_bias", "experts_gate", "experts_up",
        "experts_down"}
    # the head norms' scales are drawn about the gain (a softmax that
    # picks tokens), not all alike
    scale = np.asarray(params["layer_1"]["attn"]["q_norm"])
    assert 0.1 < np.abs(scale / arch.HEAD_NORM_GAIN - 1).mean() < 0.5
    # one slot's carry: a two-token tail whatever the buffer's length
    # beside int8 keys and values a token, a head of 64 in 128 lanes
    for l_buf in (24, 700):
        cache = jax.eval_shape(lambda: init_cache(model, 3, l_buf))
        assert {k: v.shape for k, v in cache["layer_0"]["attn"].items()} == {
            "conv": (3, 2, 256), "cache_index": ()}
        leaves = cache["layer_1"]["attn"]
        assert set(leaves) == {
            "cached_key_q", "cached_value_q", "cached_key_scale",
            "cached_value_scale", "cache_index"}
        hkv, lpad, lanes = leaves["cached_key_q"].shape[1:]
        assert (hkv, lanes) == (1, 128) and lpad >= l_buf
        assert leaves["cached_key_q"].dtype == jnp.int8


@pytest.mark.parametrize("asked,refusal", [
    ({"layer_types": ["conv", "sliding", "conv"], "window": 16},
     "one stack holds kinds of one of"),
    ({"layer_types": ["conv", "kda", "conv"]},
     "one stack holds kinds of one of"),
    ({"layer_types": ["conv", "conv", "conv"], "kv_quant": True,
      "qk_norm": False},
     "kv_quant: the attention layers' keys and values"),
    ({"window": 16}, "window on a conv layer"),
    ({"head_gate": True}, "head_gate on a conv layer"),
    ({"early_router": True, "mlp_layer_types": ["sparse"] * 3},
     "early_router on a conv layer"),
    ({"layer_types": ["conv", "conv", "conv"]},
     "qk_norm: only a retention layer and an attention layer"),
], ids=["beside_sliding", "beside_kda", "kv_quant_without_keys", "window",
        "head_gate", "early_router", "qk_norm_without_attention"])
def test_what_a_stack_of_tails_and_keys_cannot_be_is_refused(asked, refusal):
    _, _, kw = _lfm2()
    with pytest.raises(ValueError, match=refusal):
        create_model({**kw, **asked})


def test_kv_quant_beside_conv_layers_means_the_attention_layers():
    _, _, kw = _lfm2(kv_quant=True)
    model = create_model(dict(kw))
    cache = jax.eval_shape(lambda: init_cache(model, 2, 24))
    assert "cached_key_q" in cache["layer_1"]["attn"]
    assert set(cache["layer_2"]["attn"]) == {"conv", "cache_index"}
    # and a stack of conv layers alone is served, without it
    alone = create_model({**kw, "layer_types": ["conv"] * 3,
                          "kv_quant": False, "qk_norm": False})
    assert alone.attention_windows() == ()


# what "far off" is for each wrong model: the drawn bias is small
# (N(0, 0.02^2), so that it changes some choices and not all), and what
# it moves is small beside a mechanism left out; sound reads under 2e-4
FAR_OFF = {"no_qk_norm": 0.05, "no_c_gate": 0.05, "silu_after_the_conv": 0.05,
           "the_bias_as_a_weight": 0.005, "no_selection_bias": 0.005}


@pytest.mark.parametrize("reference", [
    "as_published", "no_qk_norm", "no_c_gate", "silu_after_the_conv",
    "the_bias_as_a_weight", "no_selection_bias"])
def test_both_caches_serve_lfm2s_layers_and_no_other(
        served, reference, monkeypatch):
    """The full forward, and a LEFT-padded prompt in chunks then single
    steps through BOTH caches (the tails and the keys and values),
    against ``reference/lfm2_moe.py``: float32 agrees to 2e-4; the
    reference with one mechanism undone does not agree."""
    arch, d, _ = _lfm2()
    whole, got = served
    np.testing.assert_allclose(got, whole, atol=2e-4)
    patch = {
        "no_qk_norm": ("head_norm", lambda x, scale, eps: x),
        "no_c_gate": ("out_gate", lambda gate, c: c),
        "silu_after_the_conv": ("after_conv", jax.nn.silu),
        "no_selection_bias": (
            "chosen", lambda s, bias, k: jax.lax.top_k(s, k)[1]),
    }.get(reference)
    if reference == "the_bias_as_a_weight":
        route = arch.route

        def biased(u, w, d):
            # the bias joins the weights too: sigmoid(logit) + bias is
            # what a router that adds it before the activation's use
            # would weigh by
            s = jax.nn.sigmoid(u @ w["router"]) + w["router_bias"]
            idx = jax.lax.top_k(s, d["top_k"])[1]
            picked = s * jax.nn.one_hot(idx, d["experts"]).sum(-2)
            return picked / picked.sum(-1, keepdims=True)

        assert route is not biased
        monkeypatch.setattr(arch, "route", biased)
    elif patch:
        monkeypatch.setattr(arch, *patch)
    err = np.abs(got - _reference_logits(arch, d, IDS)).max()
    if reference == "as_published":
        assert err < 2e-4
    else:
        assert err > FAR_OFF[reference]


@pytest.mark.parametrize("wrong", [
    "the_tail_dropped_at_a_chunk_boundary", "pads_in_the_conv"])
def test_a_chunk_that_loses_its_tail_or_lets_pads_in_is_not_the_layer(
        wrong, monkeypatch):
    """The wrong PROGRAM: a chunk that starts from a zero tail instead
    of the carried one, or left pads that reach the first tokens
    through the convolution, give other logits than the reference's."""
    from mlcomp_tpu.models.short_conv import GatedShortConv

    arch, d, kw = _lfm2()
    model = create_model(dict(kw))
    params = W.program_params(arch, SEED, d, jnp.float32)
    chunk = GatedShortConv._chunk

    def broken(self, u, tail, taps, valid):
        if wrong == "pads_in_the_conv":
            return chunk(self, u, tail, taps, None)
        return chunk(self, u, jnp.zeros_like(tail), taps, valid)

    monkeypatch.setattr(GatedShortConv, "_chunk", broken)
    with jax.default_matmul_precision("highest"):
        got = _served_logits(model, params, IDS, n_prompt=21)
    assert np.abs(got - _reference_logits(arch, d, IDS)).max() > 0.05


def _logprobs_of(ref_logits, tokens, first):
    """The reference's log-probability of ``tokens``, the first of them
    predicted at position ``first``."""
    lg = ref_logits[first:first + len(tokens)].astype(np.float64)
    lp = lg - np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1,
                     keepdims=True)) - lg.max(-1, keepdims=True)
    return lp[np.arange(len(tokens)), tokens]


def test_prefill_then_decode_through_generation_agrees_with_the_reference():
    """``models/generation.py``'s path: one fresh prefill of a
    LEFT-padded batch, then steps under the one ``cache_index``; each
    row's reported log-probabilities against the reference's full
    forward over that row alone, teacher-forced."""
    arch, d, kw = _lfm2()
    model = create_model(dict(kw))
    params = W.program_params(arch, SEED, d, jnp.float32)
    lens, width, n_new = (19, 11), 19, 12
    prompt = np.zeros((2, width), np.int32)
    mask = np.zeros((2, width), bool)
    for r, n in enumerate(lens):
        prompt[r, width - n:] = IDS[0, r:r + n]
        mask[r, width - n:] = True
    with jax.default_matmul_precision("highest"):
        ids, lps = generate(
            model, {"params": params}, jnp.asarray(prompt), n_new,
            prompt_mask=jnp.asarray(mask), with_logprobs=True)
    ids, lps = np.asarray(ids), np.asarray(lps)
    for r, n in enumerate(lens):
        seq = ids[r, width - n:][None]
        ref = _reference_logits(arch, d, seq)[0]
        want = _logprobs_of(ref, seq[0, n:], n - 1)
        np.testing.assert_allclose(lps[r], want, atol=3e-4)


@pytest.mark.parametrize("kv_quant", [False, True])
def test_requests_admitted_mid_flight_agree_with_the_reference(kv_quant):
    """Through ``DecodeEngine``: three slots, five requests of
    different lengths (one chunk, two and three), the later ones
    admitted while the earlier decode and into slots a finished request
    left; every request's reported log-probabilities against the
    reference's full forward over its own tokens.  With ``kv_quant``
    the reference reads keys and values rounded to int8 a token and KV
    head, as the cache holds them."""
    from benchmark.reference.quant import kv_round

    arch, d, kw = _lfm2(kv_quant=kv_quant)
    model = create_model(dict(kw))
    params = W.program_params(arch, SEED, d, jnp.float32)
    rng = np.random.RandomState(3)
    asks = [(rng.randint(1, 512, n).tolist(), m)
            for n, m in ((5, 18), (13, 9), (24, 14), (9, 6), (17, 11))]
    eng = DecodeEngine(model, {"params": params}, slots=3,
                       prompt_buckets=(32,), max_new_cap=32,
                       steps_per_dispatch=2, prefill_chunk=8)
    try:
        with jax.default_matmul_precision("highest"):
            futures = [eng.submit(ids, m, logprobs=True) for ids, m in asks]
            outs = [f.result(timeout=600) for f in futures]
            st = eng.stats()
    finally:
        eng.close()
    assert st["conv"]["state_rows"] > 0 and st["conv"]["chunk_tokens"] == 2 * (
        5 + 13 + 24 + 9 + 17)
    def errors(kv_fn):
        errs = []
        for (ids, m), out in zip(asks, outs):
            assert len(out["ids"]) == m
            seq = np.asarray([ids + out["ids"]])
            ref = _reference_logits(arch, d, seq, kv_fn)[0]
            errs.append(np.abs(
                np.asarray(out["logprobs"])
                - _logprobs_of(ref, seq[0, len(ids):], len(ids) - 1)))
        return np.concatenate(errs)

    errs = errors(kv_round("int8") if kv_quant else None)
    if kv_quant:
        # the precision below is told apart here, at float32: on the
        # chip one attention layer of five in bfloat16 hides it (the
        # mix's limits_from)
        assert errors(kv_round("int4")).mean() > 5 * errs.mean()
    # float32 through both caches: the order of the sums.  With int8
    # keys and values the reference rounds them as the cache does, and
    # what is left is the kernel's own arithmetic on them
    assert errs.max() < (1e-2 if kv_quant else 3e-4)
