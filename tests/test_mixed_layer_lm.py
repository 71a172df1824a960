"""``mixed_layer_lm`` (layers built from per-layer lists: window and
full attention with their own head counts and RoPE, a per-head gate,
dropless routed experts of which a share is held) against the plain
reference ``benchmark/reference/laguna.py``, at tiny widths on the CPU
with seeded weights."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from benchmark import weights as W
from helpers_last_logits import assert_last_logits_only_is_the_last_row
from mlcomp_tpu.models import create_model
from mlcomp_tpu.models.generation import init_cache
from mlcomp_tpu.models.moe import RoutedExperts
from mlcomp_tpu.models.transformer import (
    RopeSpec,
    SelfAttention,
    apply_rope,
    apply_rope_spec,
    rope_inv_freq,
)

ROOT = Path(__file__).resolve().parents[1]
HI = jax.lax.Precision.HIGHEST


def _cfg(name="_rehearsal/laguna-s-2_1-serve.json"):
    with open(ROOT / "benchmark" / "configs" / name) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    """(reference module, dims, model kwargs) at rehearsal width, float32."""
    cfg = _cfg()
    arch = cells.architecture(cfg)
    model = {**cfg["model"], "dtype": "float32", "head_dtype": "float32"}
    return arch, arch.dims_of(cfg), model


def _reference_logits(arch, d, seed, ids):
    key = W.seed_key(seed)
    top = arch.top_weights(key, d, jnp.float32)
    x = arch.embed(jnp.asarray(ids), top["emb"])
    pos = jnp.broadcast_to(jnp.arange(ids.shape[1])[None], ids.shape)
    for i, kind in enumerate(arch.layer_kinds(d)):
        x = arch.layer(x, arch.layer_weights(key, i, d, jnp.float32, kind),
                       pos, d, kind)
    return np.asarray(arch.logits(x, top, d))


def test_the_model_is_assembled_from_the_lists(tiny):
    arch, d, kw = tiny
    assert arch.layer_kinds(d) == ["dense_full", "sparse_sliding",
                                   "sparse_full"]
    model = create_model(dict(kw))
    assert model.attention_windows() == (None, 16, None)
    params = W.program_params(arch, 7, d, jnp.float32)
    abstract = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    W.check_layout(params, abstract)
    assert set(params["layer_1"]["moe"]) == {
        "router", "experts_gate", "experts_up", "experts_down",
        "shared_gate", "shared_up", "shared_down"}
    assert params["layer_1"]["moe"]["experts_gate"].shape == (4, 256, 128)
    assert params["layer_1"]["moe"]["router"]["kernel"].shape == (256, 8)
    assert params["layer_1"]["attn"]["q"]["kernel"].shape == (256, 3, 64)
    assert params["layer_0"]["attn"]["head_gate"]["kernel"].shape == (256, 2)
    with pytest.raises(ValueError, match="one entry a layer"):
        create_model({**kw, "heads_per_layer": [2, 3]})
    with pytest.raises(ValueError, match="not among"):
        create_model({**kw, "layer_types": ["full", "ring", "full"]})


def test_full_forward_past_the_window_agrees_with_the_reference(tiny):
    arch, d, kw = tiny
    model = create_model({**kw, "kv_quant": False})
    params = W.program_params(arch, 7, d, jnp.float32)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(0), (2, 40), 1, 512))
    with jax.default_matmul_precision("highest"):
        got, sown = model.apply({"params": params}, jnp.asarray(ids),
                                mutable=["counters"])
    np.testing.assert_allclose(
        np.asarray(got), _reference_logits(arch, d, 7, ids), atol=2e-4)
    counts = jax.tree_util.tree_leaves(sown["counters"])
    assert len(counts) == 2            # one vector a sparse layer
    for c in counts:                   # made, held, touched, calls, held here
        assert c[0] == 2 * 40 * 2 and 0 < c[1] < c[0]
        assert 1 <= c[2] <= 4 and c[3] == 1 and c[4] == 4


@pytest.mark.parametrize("kv_quant", [False, True])
def test_prefill_then_decode_through_the_cache_agrees_with_the_reference(
        tiny, kv_quant):
    """The engine's contract: a LEFT-padded prompt prefilled in chunks
    (the first fresh, the second against the cache), then single-token
    steps at a per-row cursor, contexts running past the window of 16;
    logits against the reference's full forward, no cache."""
    arch, d, kw = tiny
    model = create_model({**kw, "kv_quant": kv_quant})
    params = W.program_params(arch, 7, d, jnp.float32)
    n_prompt, bucket, chunk, n_new, l_buf = 20, 32, 16, 30, 65
    ids = np.asarray(jax.random.randint(
        jax.random.PRNGKey(0), (1, n_prompt + n_new), 1, 512))
    pad = bucket - n_prompt
    row = np.zeros((1, bucket), np.int32)
    row[0, pad:] = ids[0, :n_prompt]
    positions = np.maximum(np.arange(bucket) - pad, 0)[None].astype(np.int32)
    kv_mask = jnp.asarray((np.arange(l_buf) >= pad)[None])
    cache = init_cache(model, 1, l_buf)
    out = []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, bucket, chunk):
            lg, upd = model.apply(
                {"params": params, "cache": cache},
                jnp.asarray(row[:, lo:lo + chunk]), decode=True,
                positions=jnp.asarray(positions[:, lo:lo + chunk]),
                kv_mask=kv_mask, mutable=["cache"])
            cache = upd["cache"]
            out.append(np.asarray(lg))
        out = [np.concatenate(out, 1)[:, pad:]]
        for t in range(n_prompt, n_prompt + n_new):
            lg, upd = model.apply(
                {"params": params, "cache": cache},
                jnp.asarray(ids[:, t:t + 1]), decode=True,
                positions=jnp.full((1, 1), t, jnp.int32), kv_mask=kv_mask,
                cache_cursor=jnp.array([bucket + t - n_prompt], jnp.int32),
                mutable=["cache"])
            cache = upd["cache"]
            out.append(np.asarray(lg))
    err = np.abs(np.concatenate(out, 1)
                 - _reference_logits(arch, d, 7, ids)).max(-1)[0]
    assert not np.isnan(err).any()
    if not kv_quant:
        assert err.max() < 2e-4
    else:
        # int8 keys and values: every position off by a little, and a
        # top-2-of-8 routing flip on a near-tie now and then by a lot
        assert np.median(err) < 0.15 and (err < 0.3).mean() > 0.8


def test_a_window_layers_fresh_prefill_longer_than_its_window_is_refused(tiny):
    arch, d, kw = tiny
    model = create_model(dict(kw))
    params = W.program_params(arch, 7, d, jnp.float32)
    cache = init_cache(model, 1, 64)
    with pytest.raises(NotImplementedError, match="longer than this"):
        model.apply(
            {"params": params, "cache": cache}, jnp.ones((1, 32), jnp.int32),
            decode=True, mutable=["cache"],
            positions=jnp.arange(32, dtype=jnp.int32)[None])


def test_the_two_halves_and_the_shared_expert_once_are_the_whole_layer(tiny):
    """Guide section 4's share test: what the chip holding experts 0-3
    computes, plus what the chip holding 4-7 computes, with the shared
    expert (which both compute alike) counted once, is the uncut
    reference layer's MLP."""
    arch, d, _ = tiny
    h, f = d["hidden"], d["expert_width"]
    uncut = {**d, "held": (0, d["experts"])}
    w = arch.layer_weights(W.seed_key(3), 1, uncut, jnp.float32,
                           "sparse_sliding")
    assert w["experts_gate"].shape == (8, h, f)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 24, h), jnp.float32)
    shared = arch.swiglu(u, w["shared_gate"], w["shared_up"], w["shared_down"])
    whole = arch.routed(u, w, uncut) + shared

    def half(first):
        layer = RoutedExperts(
            n_experts=8, d_model=h, d_ff=f, k=d["top_k"],
            experts_held=(first, 4), routed_scale=d["routed_scale"],
            shared_width=d["shared_width"], dtype=jnp.float32)
        params = {
            "router": {"kernel": w["router"]},
            **{f"experts_{n}": w[f"experts_{n}"][first:first + 4]
               for n in ("gate", "up", "down")},
            **{f"shared_{n}": {"kernel": w[f"shared_{n}"]}
               for n in ("gate", "up", "down")},
        }
        with jax.default_matmul_precision("highest"):
            return layer.apply({"params": params}, u)

    np.testing.assert_allclose(
        np.asarray(half(0) + half(4) - shared), np.asarray(whole), atol=2e-5)
    # and a half alone is the reference's half
    np.testing.assert_allclose(
        np.asarray(half(4)),
        np.asarray(arch.routed(u, {**w, **{
            k: w[k][4:] for k in ("experts_gate", "experts_up",
                                  "experts_down")}}, {**d, "held": (4, 4)})
                   + shared), atol=2e-5)


def test_yarn_and_partial_rotary_angles_are_the_references():
    cfg = _cfg("laguna-s-2_1-serve.json")
    arch = cells.architecture(cfg)
    d = arch.dims_of(cfg)
    model = cfg["model"]
    full = RopeSpec.of(model["rope_full"])
    d_r, inv = arch.rope_angles(d["rope"]["full"], 128)
    assert d_r == 64 == full.rotary_dim
    np.testing.assert_allclose(rope_inv_freq(full, 128), np.asarray(inv),
                               rtol=1e-6)
    # the published numbers: the ramp runs from dimension 9 to 18
    plain = 500000.0 ** (-2.0 * np.arange(32) / 64)
    got = rope_inv_freq(full, 128)
    np.testing.assert_allclose(got[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(got[18:], plain[18:] / 128.0, rtol=1e-6)
    assert np.all(got[10:18] < plain[10:18]) and np.all(
        got[10:18] > plain[10:18] / 128.0)
    sliding = RopeSpec.of(model["rope_sliding"])
    _, inv = arch.rope_angles(d["rope"]["sliding"], 128)
    np.testing.assert_allclose(rope_inv_freq(sliding, 128), np.asarray(inv),
                               rtol=1e-6)
    # the rotation itself: 64 dimensions turn, 64 pass through
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 3, 128), jnp.float32)
    pos = jnp.asarray([[0, 1, 2, 3, 4, 5], [700, 701, 702, 703, 704, 9000]])
    np.testing.assert_allclose(
        np.asarray(apply_rope_spec(x, pos, full)),
        np.asarray(arch.rope(x, pos, d["rope"]["full"])), atol=1e-5)
    assert np.array_equal(np.asarray(apply_rope_spec(x, pos, full))[..., 64:],
                          np.asarray(x)[..., 64:])
    # a description of apply_rope's own default is apply_rope
    np.testing.assert_allclose(
        np.asarray(apply_rope_spec(x, pos, RopeSpec())),
        np.asarray(apply_rope(x, pos)), atol=1e-6)


def _masked_softmax_attention(q, k, v, lo, hi, scale):
    """q (H, dh), k/v (Hkv, L, dh) float32; keys [lo, hi)."""
    rep = q.shape[0] // k.shape[0]
    out = []
    for h in range(q.shape[0]):
        s = k[h // rep] @ q[h] * scale
        s = np.where((np.arange(len(s)) >= lo) & (np.arange(len(s)) < hi),
                     s, -np.inf)
        p = np.exp(s - s.max())
        out.append((p / p.sum()) @ v[h // rep])
    return np.stack(out)


@pytest.fixture(scope="module")
def kv8():
    from mlcomp_tpu.ops.pallas.decode_attention import quantize_kv

    b, hkv, l_buf, dh = 2, 2, 256, 128
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    k = jax.random.normal(ks[0], (b, l_buf, hkv, dh), jnp.float32)
    v = jax.random.normal(ks[1], (b, l_buf, hkv, dh), jnp.float32)
    (k8, ksc), (v8, vsc) = quantize_kv(k), quantize_kv(v)
    lay = lambda x: x.transpose(0, 2, 1, 3)                   # noqa: E731
    sc = lambda s: s.transpose(0, 2, 1)[:, :, None].astype(jnp.bfloat16)  # noqa
    deq = lambda x8, s: np.asarray(                            # noqa: E731
        lay(x8).astype(jnp.float32)
        * sc(s).astype(jnp.float32).transpose(0, 1, 3, 2))
    return (lay(k8), sc(ksc), lay(v8), sc(vsc)), deq(k8, ksc), deq(v8, vsc)


def test_the_decode_kernels_window_is_a_raised_start(kv8):
    """Single-token step: the window is ``kv_start`` raised to stop -
    window, against a masked softmax over the dequantized cache."""
    from mlcomp_tpu.ops.pallas.decode_attention import decode_attention

    bufs, k, v = kv8
    heads, dh, window = 6, 128, 48
    q = jax.random.normal(jax.random.PRNGKey(6), (2, heads, dh), jnp.float32)
    first = jnp.asarray([3, 140], jnp.int32)
    stop = jnp.asarray([200, 170], jnp.int32)
    start = jnp.maximum(first, stop - window)
    got = np.asarray(decode_attention(
        q, *bufs, kv_start=start, kv_stop=stop, interpret=True))
    for r in range(2):
        want = _masked_softmax_attention(
            np.asarray(q[r]), k[r], v[r], int(start[r]), int(stop[r]),
            dh ** -0.5)
        np.testing.assert_allclose(got[r], want, atol=2e-2)
    assert int(start[0]) == 152 and int(start[1]) == 140  # one of each


@pytest.mark.parametrize("s_q", [8, 40])
def test_the_chunk_kernels_window_is_a_start_per_query(kv8, s_q):
    """Chunk against the cache: query j sees [max(start, stop0 + j -
    window), stop0 + j), a lower bound of its own per sublane row; 40
    queries take two query tiles."""
    from mlcomp_tpu.ops.pallas.decode_attention import decode_attention_chunk

    bufs, k, v = kv8
    heads, dh, window = 6, 128, 48
    q = jax.random.normal(jax.random.PRNGKey(7), (2, s_q, heads, dh),
                          jnp.float32)
    first = jnp.asarray([3, 150], jnp.int32)
    stop0 = jnp.asarray([180, 160], jnp.int32)
    got = np.asarray(decode_attention_chunk(
        q, *bufs, kv_start=first, kv_stop0=stop0, window=window,
        interpret=True))
    whole = np.asarray(decode_attention_chunk(
        q, *bufs, kv_start=first, kv_stop0=stop0, interpret=True))
    differs = False
    for r in range(2):
        for j in range(s_q):
            hi = int(stop0[r]) + j
            lo = max(int(first[r]), hi - window)
            want = _masked_softmax_attention(
                np.asarray(q[r, j]), k[r], v[r], lo, hi, dh ** -0.5)
            np.testing.assert_allclose(got[r, j], want, atol=2e-2)
            if lo > int(first[r]):
                differs |= bool(np.abs(got[r, j] - whole[r, j]).max() > 1e-3)
    assert differs  # the window took keys away from the long row


INTERNLM2_REHEARSAL = dict(hidden=256, heads=2, kv_heads=1)


@pytest.mark.parametrize("kv_quant", [False, True])
def test_self_attention_with_default_fields_is_the_module_it_was(kv_quant):
    """InternLM2's rehearsal size.  The new fields at their defaults
    leave the parameter paths where they were, and the same attention
    described through the fields (head width, RoPE base, a window no
    context reaches, no gate) is bit-identical to the defaults: the
    general paths compute what the fixed ones did."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 256), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(24)[None], (2, 24))
    plain = SelfAttention(dtype=jnp.float32, kv_quant=kv_quant,
                          **INTERNLM2_REHEARSAL)
    params = plain.init(jax.random.PRNGKey(0), x, pos)["params"]
    assert set(params) == {"RMSNorm_0", "q", "k", "v", "out"}
    assert params["q"]["kernel"].shape == (256, 2, 128)
    described = SelfAttention(
        dtype=jnp.float32, kv_quant=kv_quant, head_dim=128,
        rope=RopeSpec(base=10000.0), window=4096, **INTERNLM2_REHEARSAL)
    a = plain.apply({"params": params}, x, pos)
    b = described.apply({"params": params}, x, pos)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    # through the cache: prefill 16, then a chunk of 4, then single steps
    def served(module):
        cache = module.init(jax.random.PRNGKey(0), jnp.zeros((2, 40, 256)),
                            jnp.zeros((2, 40), jnp.int32), decode=True)["cache"]
        out = []
        for lo, hi in [(0, 16), (16, 20)] + [(t, t + 1) for t in range(20, 24)]:
            y, upd = module.apply(
                {"params": params, "cache": cache}, x[:, lo:hi], pos[:, lo:hi],
                decode=True, mutable=["cache"])
            cache = upd["cache"]
            out.append(np.asarray(y))
        return np.concatenate(out, 1)

    assert np.array_equal(served(plain), served(described))


# ---- what SmallThinker-21BA3B adds: layers that rotate nothing, a
# router fed the attention's own normed input, a ReLU gate ----

def _smallthinker():
    cfg = _cfg("_rehearsal/smallthinker-21ba3b-serve.json")
    arch = cells.architecture(cfg)
    model = {**cfg["model"], "dtype": "float32", "head_dtype": "float32"}
    return arch, arch.dims_of(cfg), model


@pytest.mark.parametrize("asked,refusal", [
    ({"early_router": True,
      "mlp_layer_types": ["dense", "sparse"], "mlp_dim": 256},
     "early_router: a dense MLP has no router"),
    ({"expert_gate": "gelu"}, "expert_gate 'gelu'"),
], ids=["early_router_on_a_dense_layer", "a_gate_the_kernel_lacks"])
def test_what_the_model_cannot_be_is_refused_at_create_model(asked, refusal):
    _, _, kw = _smallthinker()
    with pytest.raises(ValueError, match=refusal):
        create_model({**kw, **asked})


def test_smallthinkers_tree_keeps_the_attention_norm_where_it_was():
    arch, d, kw = _smallthinker()
    assert arch.layer_kinds(d) == ["full", "sliding"]
    model = create_model(dict(kw))
    assert model.attention_windows() == (None, 16)
    params = W.program_params(arch, 7, d, jnp.float32)
    abstract = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    W.check_layout(params, abstract)
    assert set(params["layer_0"]) == {"attn", "RMSNorm_0", "moe"}
    assert set(params["layer_0"]["attn"]) == {"RMSNorm_0", "q", "k", "v", "out"}
    assert set(params["layer_0"]["moe"]) == {
        "router", "experts_gate", "experts_up", "experts_down"}


def test_only_the_layers_that_rotate_carry_a_rope_scope():
    """The global layer (``rotary_dim`` 0) has no op under ``attn.rope``;
    the window layer has; both route under ``moe.route``."""
    import re

    _, _, kw = _smallthinker()
    model = create_model({**kw, "kv_quant": False})
    abstract = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    text = jax.jit(lambda p, x: model.apply({"params": p}, x)).lower(
        abstract, jax.ShapeDtypeStruct((1, 8), jnp.int32)
    ).as_text(debug_info=True)
    assert set(re.findall(r"(layer_\d+)/attn/[\w./]*attn\.rope/", text)) == {
        "layer_1"}
    assert set(re.findall(r"(layer_\d+)/moe/moe\.route/", text)) == {
        "layer_0", "layer_1"}


def test_the_published_gate_is_the_one_routed_experts_computes():
    """Top k by logit, then the softmax over those k (the published
    form, ``reference/smallthinker.py``) against the softmax over all,
    its top k, renormalised (``RoutedExperts``): the same experts at
    the same weights."""
    arch, d, _ = _smallthinker()
    r_in = jax.random.normal(jax.random.PRNGKey(4), (3, 50, 256), jnp.float32)
    router = jax.random.normal(jax.random.PRNGKey(5), (256, 8), jnp.float32)
    published = np.asarray(arch.route(r_in, router, d))
    logit = jnp.einsum("bsd,de->bse", r_in, router, precision=HI)
    top, idx = jax.lax.top_k(jax.nn.softmax(logit, axis=-1), d["top_k"])
    gates = top / jnp.sum(top, axis=-1, keepdims=True)
    computed = np.asarray(jnp.sum(
        jax.nn.one_hot(idx, 8, dtype=jnp.float32) * gates[..., None], -2))
    assert ((published > 0) == (computed > 0)).all()
    assert ((published > 0).sum(-1) == d["top_k"]).all()
    np.testing.assert_allclose(computed, published, atol=1e-6)


def _wrong_model(arch, d, name):
    """The reference with one of SmallThinker's three departures from
    the other served models undone."""
    if name == "router_after_attention":
        return d, {"router_input": lambda h, u: u}
    if name == "silu_gate":
        return d, {"relu": jax.nn.silu}
    if name == "global_layer_rotates":
        return {**d, "rotates": {"sliding": True, "full": True}}, {}
    return d, {}


@pytest.mark.parametrize("reference", [
    "as_published", "router_after_attention", "silu_gate",
    "global_layer_rotates"])
def test_the_full_forward_is_smallthinkers_and_no_other_models(
        reference, monkeypatch):
    arch, d, kw = _smallthinker()
    model = create_model({**kw, "kv_quant": False})
    params = W.program_params(arch, 7, d, jnp.float32)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(0), (2, 40), 1, 512))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params}, jnp.asarray(ids)))
    d, patches = _wrong_model(arch, d, reference)
    for name, fn in patches.items():
        monkeypatch.setattr(arch, name, fn)
    err = np.abs(got - _reference_logits(arch, d, 7, ids)).max()
    if reference == "as_published":
        assert err < 2e-4
    else:
        assert err > 0.05


# the (path, shape) list of the parameter tree, hashed, and the programs
# the engine builds while one request is admitted alone and a second
# joins it: both as the commit before SmallThinker built them
AS_IT_WAS = {
    "internlm2-1_8b-serve": (21, "e68fcc57a2bb16bc", 32),
    "laguna-s-2_1-serve": (41, "9b07763613eed0be", 16),
    # as the commit before Kimi-Linear built them (the mixer table, the
    # router's score and bias are fields those models leave alone)
    "smallthinker-21ba3b-serve": (23, "ba207b5d14ac2654", 16),
    "brumby-14b-serve": (29, "873959174c3da4c8", 16),
}


@pytest.mark.parametrize("name", sorted(AS_IT_WAS))
def test_the_other_served_models_build_what_they_built(name):
    import hashlib

    from mlcomp_tpu.serve import GenerationService

    cfg = _cfg(f"_rehearsal/{name}.json")
    model = create_model(dict(cfg["model"]))
    abstract = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    leaves = sorted(
        (jax.tree_util.keystr(p), tuple(x.shape))
        for p, x in jax.tree_util.tree_leaves_with_path(abstract))
    digest = hashlib.sha1(repr(leaves).encode()).hexdigest()[:16]
    n_leaves, was, chunk = AS_IT_WAS[name]
    assert (len(leaves), digest) == (n_leaves, was)

    params = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.bfloat16), abstract)
    service = GenerationService(
        model, {"params": params}, seed=1, metrics_history_interval=None,
        batcher="continuous",
        **{k: tuple(v) if isinstance(v, list) else v
           for k, v in {**cfg["service"], "max_new_buckets": [128]}.items()})
    try:
        # the second is sent right behind the first, which decodes for 32
        # dispatches: it joins a row that decodes however loaded the host
        first = service.submit(list(range(1, 20)), 128, temperature=0.0)
        service.submit(list(range(1, 9)), 8, temperature=0.0).result(
            timeout=600)
        first.result(timeout=600)
        programs = set(service.engine._fns)
    finally:
        service.close()
    assert programs == {
        ("dispatch", 4), ("dispatch_core", 4), ("fused_dispatch", chunk, 4),
        ("prefill_chunk", chunk), "insert", "prefill_init"}


# ---- what Brumby-14B adds: a third layer kind that keeps a recurrent
# state and no keys and values (models/retention.py) ----

def _brumby():
    cfg = _cfg("_rehearsal/brumby-14b-serve.json")
    arch = cells.architecture(cfg)
    model = {**cfg["model"], "dtype": "float32", "head_dtype": "float32"}
    return arch, arch.dims_of(cfg), model


def test_a_retention_stack_is_assembled_from_the_lists():
    arch, d, kw = _brumby()
    assert arch.layer_kinds(d) == ["retention", "retention"]
    model = create_model(dict(kw))
    # no attention layer reads context tokens
    assert model.attention_windows() == ()
    params = W.program_params(arch, 7, d, jnp.float32)
    abstract = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    W.check_layout(params, abstract)
    assert set(params["layer_0"]) == {"attn", "RMSNorm_0", "gate", "up",
                                      "down"}
    assert set(params["layer_0"]["attn"]) == {
        "RMSNorm_0", "q", "k", "v", "out", "gate", "q_norm", "k_norm"}
    np.testing.assert_allclose(
        params["layer_1"]["attn"]["gate"]["bias"], [3.0, 8.0])
    # the cache is state of a fixed size, whatever the buffer's length
    for l_buf in (24, 4353):
        cache = jax.eval_shape(lambda: init_cache(model, 3, l_buf))
        assert {k: v.shape for k, v in cache["layer_0"]["attn"].items()} == {
            "state": (3, 2, 9 * 16, 16), "norm": (3, 2, 9 * 16),
            "cache_index": ()}


@pytest.mark.parametrize("asked,refusal", [
    ({"kv_quant": True}, "kv_quant on a retention layer"),
    ({"window": 16}, "window on a retention layer"),
    ({"layer_types": ["retention", "full"]},
     "one stack holds kinds of one of"),
    ({"layer_types": ["kda", "kda"]}, "qk_norm: only a retention layer"),
], ids=["kv_quant", "window", "beside_attention", "qk_norm_on_kda"])
def test_what_a_retention_stack_cannot_be_is_refused_at_create_model(
        asked, refusal):
    _, _, kw = _brumby()
    with pytest.raises(ValueError, match=refusal):
        create_model({**kw, **asked})


def _served_logits(model, params, ids, n_prompt, bucket=32, chunk=8, l_buf=65):
    """The engine's contract on one row: a LEFT-padded prompt in chunks
    (pads and tokens share a chunk), then single-token steps at a
    cursor; the logits of the real positions."""
    pad = bucket - n_prompt
    row = np.zeros((1, bucket), np.int32)
    row[0, pad:] = ids[0, :n_prompt]
    positions = np.maximum(np.arange(bucket) - pad, 0)[None].astype(np.int32)
    kv_mask = jnp.asarray((np.arange(l_buf) >= pad)[None])
    cache = init_cache(model, 1, l_buf)
    out = []
    for lo in range(0, bucket, chunk):
        lg, upd = model.apply(
            {"params": params, "cache": cache},
            jnp.asarray(row[:, lo:lo + chunk]), decode=True,
            positions=jnp.asarray(positions[:, lo:lo + chunk]),
            kv_mask=kv_mask, mutable=["cache", "counters"])
        cache = upd["cache"]
        out.append(np.asarray(lg))
    out = [np.concatenate(out, 1)[:, pad:]]
    for t in range(n_prompt, ids.shape[1]):
        lg, upd = model.apply(
            {"params": params, "cache": cache},
            jnp.asarray(ids[:, t:t + 1]), decode=True,
            positions=jnp.full((1, 1), t, jnp.int32), kv_mask=kv_mask,
            cache_cursor=jnp.array([bucket + t - n_prompt], jnp.int32),
            mutable=["cache", "counters"])
        cache = upd["cache"]
        out.append(np.asarray(lg))
    return np.concatenate(out, 1)


@pytest.mark.parametrize("reference", [
    "as_published", "gates_of_one", "no_normaliser", "degree_one"])
def test_the_three_forms_are_brumbys_layer_and_no_other(
        reference, monkeypatch):
    """The full forward (the quadratic form), and chunks then single
    steps through the state, against ``reference/brumby.py``; the
    reference with its gate, its normaliser or its degree undone does
    not agree."""
    arch, d, kw = _brumby()
    model = create_model(dict(kw))
    params = W.program_params(arch, 7, d, jnp.float32)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(0), (1, 50), 1, 512))
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(model.apply({"params": params}, jnp.asarray(ids)))
        served = _served_logits(model, params, ids, n_prompt=21)
    np.testing.assert_allclose(served, whole, atol=2e-4)
    patch = {
        "gates_of_one": ("log_gate", jnp.zeros_like),
        "no_normaliser": ("normalised", lambda num, den: num),
        "degree_one": ("power", lambda dots: dots),
    }.get(reference)
    if patch:
        monkeypatch.setattr(arch, *patch)
    err = np.abs(served - _reference_logits(arch, d, 7, ids)).max()
    if reference == "as_published":
        assert err < 2e-4
    else:
        assert err > 0.05


def test_a_chunk_that_decays_through_its_pads_is_not_the_layer(monkeypatch):
    """The wrong PROGRAM: left pads that decay the state (and add their
    keys) give other logits than the reference's."""
    from mlcomp_tpu.models.retention import PowerRetention

    arch, d, kw = _brumby()
    model = create_model(dict(kw))
    params = W.program_params(arch, 7, d, jnp.float32)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(0), (1, 50), 1, 512))
    monkeypatch.setattr(PowerRetention, "_masked",
                        staticmethod(lambda k, log_g, valid: (k, log_g)))
    with jax.default_matmul_precision("highest"):
        served = _served_logits(model, params, ids, n_prompt=21)
    assert np.abs(served - _reference_logits(arch, d, 7, ids)).max() > 0.05


# ---- last_logits_only: the final norm and the head on the row kept ----

@pytest.mark.parametrize("served, kv_quant", [
    ("laguna-s-2_1", False), ("laguna-s-2_1", True),
    ("smallthinker-21ba3b", False), ("smallthinker-21ba3b", True),
    # a retention stack keeps a state, not keys and values: one form
    ("brumby-14b", False),
    # states beside a latent: one form too
    ("kimi-linear-48b-a3b", False),
    # a convolution's tail beside keys and values, bfloat16 and int8
    ("lfm2-24b-a2b", False), ("lfm2-24b-a2b", True)])
def test_last_logits_only_is_the_full_calls_last_row(served, kv_quant):
    """Each served stack, two chunks of 16 (window and full attention
    with a cache behind the second; the experts' and the retention
    layers' sown counts; a recurrent state for a cache): the keyword
    cuts the sequence AFTER the last layer, so nothing but the logits'
    shape can tell the two calls apart."""
    cfg = _cfg(f"_rehearsal/{served}-serve.json")
    arch = cells.architecture(cfg)
    d = arch.dims_of(cfg)
    model = create_model({**cfg["model"], "dtype": "float32",
                          "head_dtype": "float32", "kv_quant": kv_quant})
    params = W.program_params(arch, 11, d, jnp.float32)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 1,
                             cfg["vocab_size"])
    full, _ = assert_last_logits_only_is_the_last_row(
        model, {"params": params}, ids, chunk=16, l_buf=48)
    sown = jax.tree_util.tree_leaves(full[-1][1].get("counters", {}))
    assert sown, "these stacks count: the counters' channel was compared"



# ---- what LFM2-24B-A2B adds: a fourth group of kinds one stack may
# hold, a convolution's tail beside keys and values
# (models/short_conv.py; the model's own tests are test_lfm2_moe.py) ----

CONV_AND_FULL = {
    "name": "mixed_layer_lm", "vocab_size": 64, "hidden": 64, "head_dim": 16,
    "kv_heads": 2, "layer_types": ["conv", "full", "conv"],
    "heads_per_layer": [4, 4, 4], "mlp_layer_types": ["dense"] * 3,
    "mlp_dim": 128, "conv_taps": 3, "dtype": "float32",
}


@pytest.mark.parametrize("asked", [
    {}, {"kv_quant": True}, {"qk_norm": True},
    {"kv_quant": True, "qk_norm": True},
    {"layer_types": ["conv", "conv", "conv"]},
    {"layer_types": ["full", "full", "full"], "qk_norm": True}],
    ids=["plain", "kv_quant", "qk_norm", "both", "conv_alone",
         "qk_norm_on_attention_alone"])
def test_full_beside_conv_builds(asked):
    from mlcomp_tpu.models.mixed_layer_lm import (
        SERVED_TOGETHER,
        STATE_KINDS,
    )

    assert ("full", "conv") in SERVED_TOGETHER and "conv" in STATE_KINDS
    model = create_model({**CONV_AND_FULL, **asked})
    kinds = list(model.layer_types)
    assert model.attention_windows() == (None,) * kinds.count("full")
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    for i, kind in enumerate(kinds):
        attn = set(params[f"layer_{i}"]["attn"])
        if kind == "conv":
            assert attn == {"RMSNorm_0", "in", "conv", "out"}
        else:
            assert ("q_norm" in attn) == bool(asked.get("qk_norm"))
    cache = jax.eval_shape(lambda: init_cache(model, 2, 24))
    for i, kind in enumerate(kinds):
        leaves = set(cache[f"layer_{i}"]["attn"])
        if kind == "conv":
            assert leaves == {"conv", "cache_index"}
        else:
            assert ("cached_key_q" in leaves) == bool(asked.get("kv_quant"))


@pytest.mark.parametrize("asked,refusal", [
    ({"layer_types": ["sliding", "conv", "conv"], "window": 8},
     "one stack holds kinds of one of"),
    ({"layer_types": ["kda", "conv", "conv"]},
     "one stack holds kinds of one of"),
    ({"layer_types": ["conv", "latent", "conv"]},
     "one stack holds kinds of one of"),
    ({"layer_types": ["conv", "retention", "conv"]},
     "one stack holds kinds of one of"),
    ({"layer_types": ["conv", "conv", "conv"], "kv_quant": True},
     r"kv_quant: the attention layers' keys and values \('full', "
     r"'sliding'\) are what it quantizes, and this stack has none"),
    ({"layer_types": ["conv", "conv", "conv"], "qk_norm": True},
     "qk_norm: only a retention layer and an attention layer"),
    ({"window": 8},
     r"window on a conv layer: it reads its last taps and nothing else "
     r"\(window is the attention layers', \('full', 'sliding'\)\)"),
    ({"head_gate": True}, "head_gate on a conv layer"),
], ids=["sliding_beside_conv", "kda_beside_conv", "latent_beside_conv",
        "retention_beside_conv", "kv_quant_without_keys_and_values",
        "qk_norm_without_q_and_k", "window", "head_gate"])
def test_what_full_beside_conv_cannot_be_is_refused_by_name(asked, refusal):
    with pytest.raises(ValueError, match=refusal):
        create_model({**CONV_AND_FULL, **asked})


# ---- what LongCat-Flash adds: a third MLP kind, the shortcut layer
# (the model's own tests are test_longcat_flash.py) ----

SHORTCUT = {
    "name": "mixed_layer_lm", "vocab_size": 64, "hidden": 128, "head_dim": 16,
    "kv_heads": 4, "layer_types": ["latent", "latent"],
    "heads_per_layer": [4, 4], "mlp_layer_types": ["shortcut"] * 2,
    "mlp_dim": 128, "experts": 8, "zero_experts": 4, "experts_per_token": 3,
    "experts_held": [0, 4], "renormalise": False, "expert_width": 128,
    "rope_full": {"base": 1e7}, "latent_dims": [16, 8, 16, 32],
    "latent_q_rank": 24, "latent_lora_scales": [True, True],
    "dtype": "float32",
}


def test_a_shortcut_layer_builds_beside_the_other_mlp_kinds():
    from mlcomp_tpu.models.mixed_layer_lm import MLP_KINDS, SHORTCUT_MIXERS

    assert MLP_KINDS == {"dense": 1, "sparse": 1, "shortcut": 2}
    assert SHORTCUT_MIXERS == ("latent",)
    model = create_model({**SHORTCUT, "layer_types": ["latent"] * 3,
                          "heads_per_layer": [4] * 3,
                          "mlp_layer_types": ["dense", "shortcut", "sparse"]})
    # one entry a mixer: 1 + 2 + 1
    assert model.attention_windows() == (None,) * 4
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert set(params["layer_0"]) == {"attn", "RMSNorm_0", "gate", "up",
                                      "down"}
    assert set(params["layer_1"]) == {
        "attn", "RMSNorm_0", "moe", "gate", "up", "down",
        "attn_1", "RMSNorm_1", "gate_1", "up_1", "down_1"}
    assert set(params["layer_2"]) == {"attn", "RMSNorm_0", "moe"}
    cache = jax.eval_shape(lambda: init_cache(model, 2, 24))
    assert set(cache["layer_1"]) == {"attn", "attn_1"}
    assert set(cache["layer_2"]) == {"attn"}


@pytest.mark.parametrize("asked,refusal", [
    ({"early_router": True},
     "early_router on a latent layer: it hands no normed input on"),
    ({"kv_quant": True},
     "kv_quant on a latent layer: the latent is kept as it is"),
    ({"window": 8}, "window on a latent layer: it reads the whole context"),
    ({"layer_types": ["full", "full"]},
     r"a shortcut layer of \['full'\] mixers: its two mixers keep two "
     r"caches of one kind in one layer's carry, which is served with "
     r"\('latent',\) alone"),
    ({"layer_types": ["kda", "latent"]},
     r"a shortcut layer of \['kda'\] mixers"),
    ({"mlp_layer_types": ["shortcut", "shortcircuit"]},
     r"mlp_layer_types: \['shortcircuit'\] not among \('dense', "
     r"'sparse', 'shortcut'\)"),
    ({"latent_q_rank": None},
     "latent_lora_scales: the query's scale is .* no latent_q_rank"),
], ids=["early_router", "kv_quant", "window", "attention_mixers",
        "kda_mixers", "unknown_mlp_kind", "a_scale_without_its_rank"])
def test_what_a_shortcut_layer_cannot_take_is_refused_by_name(asked, refusal):
    with pytest.raises(ValueError, match=refusal):
        create_model({**SHORTCUT, **asked})


def test_self_attention_with_qk_norm_norms_a_head_before_the_rotation():
    """``qk_norm`` is two learned vectors a head width and nothing
    else: RMS-normed q and k, by hand, through the module without the
    field is not possible (the norm sits between the projection and the
    rotation), so the formula is held to ``reference/lfm2_moe.py`` in
    ``test_lfm2_moe.py``; here: the field adds the two leaves, moves
    the output, and the scales move it again."""
    kw = dict(hidden=64, heads=4, kv_heads=2, dtype=jnp.float32,
              head_dim=16, rope=RopeSpec(base=1e6))
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 64))
    pos = jnp.arange(12)[None]
    plain = SelfAttention(**kw)
    normed = SelfAttention(**kw, qk_norm=True)
    p0 = plain.init(jax.random.PRNGKey(1), x, pos)["params"]
    p1 = normed.init(jax.random.PRNGKey(1), x, pos)["params"]
    assert set(p1) - set(p0) == {"q_norm", "k_norm"}
    assert p1["q_norm"].shape == (16,) and p1["k_norm"].dtype == jnp.float32
    drawn = {**p1, "q_norm": 1 + 0.3 * jnp.sin(jnp.arange(16.0)),
             "k_norm": 1 + 0.3 * jnp.cos(jnp.arange(16.0))}
    outs = [np.asarray(m.apply({"params": p}, x, pos))
            for m, p in ((plain, p0), (normed, p1), (normed, drawn))]
    assert np.abs(outs[0] - outs[1]).max() > 1e-3
    assert np.abs(outs[1] - outs[2]).max() > 1e-3
