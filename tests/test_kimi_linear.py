"""``mixed_layer_lm`` as Kimi-Linear-48B-A3B-Instruct's layers: two
more layer kinds in ONE stack, a delta-rule state (``models/kda.py``)
beside a latent a token (``models/latent_attention.py``), and a sigmoid
router with a selection bias, against the plain reference
``benchmark/reference/kimi_linear.py`` at tiny widths on the CPU with
seeded weights.  A file of its own beside ``test_mixed_layer_lm.py``:
the tier-1 command deals FILES to its workers, and that one is the
longest already."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from benchmark import weights as W
from mlcomp_tpu.models import create_model
from mlcomp_tpu.models.generation import init_cache
from mlcomp_tpu.models.moe import RoutedExperts

ROOT = Path(__file__).resolve().parents[1]


def _kimi():
    with open(ROOT / "benchmark/configs/_rehearsal"
              / "kimi-linear-48b-a3b-serve.json") as f:
        cfg = json.load(f)
    arch = cells.architecture(cfg)
    model = {**cfg["model"], "dtype": "float32", "head_dtype": "float32"}
    return arch, arch.dims_of(cfg), model


IDS = np.asarray(jax.random.randint(jax.random.PRNGKey(0), (1, 50), 1, 512))


def _reference_logits(arch, d, seed, ids):
    key = W.seed_key(seed)
    top = arch.top_weights(key, d, jnp.float32)
    x = arch.embed(jnp.asarray(ids), top["emb"])
    pos = jnp.broadcast_to(jnp.arange(ids.shape[1])[None], ids.shape)
    for i, kind in enumerate(arch.layer_kinds(d)):
        x = arch.layer(x, arch.layer_weights(key, i, d, jnp.float32, kind),
                       pos, d, kind)
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
    arch, d, kw = _kimi()
    model = create_model(dict(kw))
    params = W.program_params(arch, 7, d, jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)))
        return whole, _served_logits(model, params, IDS, n_prompt=21)


def test_a_stack_of_states_and_latents_is_assembled_from_the_lists():
    arch, d, kw = _kimi()
    assert arch.layer_kinds(d) == ["kda_dense", "kda", "kda", "latent", "kda"]
    model = create_model(dict(kw))
    # one layer reads context tokens, all of them; four read a state
    assert model.attention_windows() == (None,)
    params = W.program_params(arch, 7, d, jnp.float32)
    abstract = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    W.check_layout(params, abstract)
    assert set(params["layer_0"]) == {"attn", "RMSNorm_0", "gate", "up",
                                      "down"}
    assert set(params["layer_1"]) == {"attn", "RMSNorm_0", "moe"}
    assert set(params["layer_1"]["attn"]) == {
        "RMSNorm_0", "q", "k", "v", "conv", "decay_a", "decay_b", "A_log",
        "dt_bias", "beta", "gate_a", "gate_b", "o_norm", "out"}
    assert set(params["layer_3"]["attn"]) == {
        "RMSNorm_0", "q", "kv_a", "kv_norm", "kv_b", "out"}
    assert "router_bias" in params["layer_3"]["moe"]
    # one slot's carry holds both kinds of cache: a state whatever the
    # buffer's length, a latent a slot of the buffer (whole blocks and
    # lanes), and no leaf with a head axis of keys or values
    for l_buf, slots in ((24, 32), (9729, 10240)):
        cache = jax.eval_shape(lambda: init_cache(model, 3, l_buf))
        assert {k: v.shape for k, v in cache["layer_2"]["attn"].items()} == {
            "state": (3, 4, 16, 16), "conv": (3, 3, 3 * 4 * 16),
            "cache_index": ()}
        assert {k: v.shape for k, v in cache["layer_3"]["attn"].items()} == {
            "cached_latent": (3, slots, 128), "cache_index": ()}


@pytest.mark.parametrize("asked,refusal", [
    ({"kv_quant": True}, "kv_quant on a kda layer"),
    ({"kv_quant": True, "layer_types": ["latent"] * 5},
     "kv_quant on a latent layer: the latent is kept as it is"),
    ({"window": 16}, "window on a kda layer"),
    ({"head_gate": True}, "head_gate on a kda layer"),
    ({"early_router": True, "layer_types": ["latent"] * 5},
     "early_router on a latent layer"),
    ({"layer_types": ["kda", "full", "kda", "latent", "kda"]},
     "one stack holds kinds of one of"),
    ({"layer_types": ["kda", "kda", "retention", "latent", "kda"]},
     "one stack holds kinds of one of"),
    ({"layer_types": ["kda", "kda", "mamba", "latent", "kda"]},
     r"layer_types: \['mamba'\] not among"),
    ({"router_score": "tanh"}, "router_score 'tanh'"),
    ({"qk_norm": True}, "qk_norm: only a retention layer"),
], ids=["kv_quant_kda", "kv_quant_latent", "window", "head_gate",
        "early_router", "beside_attention", "beside_retention",
        "unknown_kind", "router_score", "qk_norm"])
def test_what_a_stack_of_states_and_latents_cannot_be_is_refused(
        asked, refusal):
    _, _, kw = _kimi()
    with pytest.raises(ValueError, match=refusal):
        create_model({**kw, **asked})


def _rotated(k_pe, positions):
    """The shared key part as a rotated model would keep it."""
    half = k_pe.shape[-1] // 2
    inv = 10000.0 ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * inv
    x1, x2 = k_pe[..., :half], k_pe[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


@pytest.mark.parametrize("reference", [
    "as_published", "no_erase", "a_scalar_decay", "rotated_k_pe",
    "softmax_router", "no_selection_bias"])
def test_both_caches_serve_kimi_linears_layers_and_no_other(
        served, reference, monkeypatch):
    """The full forward, and a LEFT-padded prompt in chunks then single
    steps through BOTH caches (the delta-rule states and the latent),
    against ``reference/kimi_linear.py``: float32 agrees to 2e-4 (sums
    taken in another order: a chunk's triangular solve against a
    token-by-token recurrence, absorbed products against expanded
    ones); the reference with one mechanism undone does not agree."""
    arch, d, _ = _kimi()
    whole, got = served
    np.testing.assert_allclose(got, whole, atol=2e-4)
    patch = {
        "no_erase": ("erased", lambda state, k: jnp.zeros_like(k)),
        "a_scalar_decay": ("log_decay", lambda a_log, pre: jnp.mean(
            -jnp.exp(a_log)[:, None] * jax.nn.softplus(pre), -1,
            keepdims=True) + 0.0 * pre),
        "rotated_k_pe": ("shared_key", _rotated),
        "softmax_router": ("router_scores",
                           lambda logits: jax.nn.softmax(logits, -1)),
    }.get(reference)
    if reference == "no_selection_bias":
        route = arch.route
        monkeypatch.setattr(arch, "route", lambda u, w, d: route(
            u, {**w, "router_bias": jnp.zeros_like(w["router_bias"])}, d))
    elif patch:
        monkeypatch.setattr(arch, *patch)
    err = np.abs(got - _reference_logits(arch, d, 7, IDS)).max()
    if reference == "as_published":
        assert err < 2e-4
    else:
        assert err > 0.05


@pytest.mark.parametrize("wrong", ["pads_decay_and_write", "pads_in_the_conv"])
def test_a_chunk_that_lets_its_pads_in_is_not_the_layer(wrong, monkeypatch):
    """The wrong PROGRAM: left pads that decay the state and write into
    it, or that reach the first tokens through the convolution, give
    other logits than the reference's."""
    from mlcomp_tpu.models.kda import KimiDeltaAttention

    arch, d, kw = _kimi()
    model = create_model(dict(kw))
    params = W.program_params(arch, 7, d, jnp.float32)
    chunk = KimiDeltaAttention._chunk

    def unmasked(self, streams, tail, taps, log_a, beta, valid, state):
        if wrong == "pads_in_the_conv" and valid is not None:
            log_a = jnp.where(valid[..., None, None], log_a, 0.0)
            beta = jnp.where(valid[..., None], beta, 0.0)
        return chunk(self, streams, tail, taps, log_a, beta, None, state)

    monkeypatch.setattr(KimiDeltaAttention, "_chunk", unmasked)
    with jax.default_matmul_precision("highest"):
        got = _served_logits(model, params, IDS, n_prompt=21)
    assert np.abs(got - _reference_logits(arch, d, 7, IDS)).max() > 0.05


def test_the_eight_shares_and_the_shared_expert_once_are_the_whole_layer():
    """Guide section 4's share test at Kimi-Linear's cut: eight chips
    share a layer, each holds an eighth of the experts; what the eight
    compute, with the shared expert (which all compute alike) counted
    once, is the uncut reference layer's MLP: sigmoid scores, the
    selection bias, the top two of all eight."""
    arch, d, _ = _kimi()
    h, f = d["hidden"], d["expert_width"]
    assert d["experts"] == 8
    uncut = {**d, "held": (0, 8)}
    w = arch.layer_weights(W.seed_key(3), 1, uncut, jnp.float32, "kda")
    # a bias large enough to change choices, as the drawn one is small
    w["router_bias"] = 0.3 * jnp.cos(jnp.arange(8.0))
    assert w["experts_gate"].shape == (8, h, f)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 24, h), jnp.float32)
    shared = arch.swiglu(u, w["shared_gate"], w["shared_up"], w["shared_down"])
    whole = arch.routed(u, w, uncut) + shared
    moved = arch.route(u, {**w, "router_bias": 0 * w["router_bias"]}, uncut)
    assert np.abs(np.asarray(arch.route(u, w, uncut) - moved)).max() > 0.1

    def share(first):
        layer = RoutedExperts(
            n_experts=8, d_model=h, d_ff=f, k=d["top_k"],
            experts_held=(first, 1), routed_scale=d["routed_scale"],
            shared_width=f, dtype=jnp.float32, router_score="sigmoid",
            selection_bias=True)
        params = {
            "router": {"kernel": w["router"]},
            "router_bias": w["router_bias"],
            **{f"experts_{n}": w[f"experts_{n}"][first:first + 1]
               for n in ("gate", "up", "down")},
            **{f"shared_{n}": {"kernel": w[f"shared_{n}"]}
               for n in ("gate", "up", "down")},
        }
        with jax.default_matmul_precision("highest"):
            return layer.apply({"params": params}, u)

    parts = [share(first) for first in range(8)]
    np.testing.assert_allclose(
        np.asarray(sum(parts) - 7 * shared), np.asarray(whole), atol=3e-5)
    # and a share alone is the reference's share
    np.testing.assert_allclose(
        np.asarray(parts[5]),
        np.asarray(arch.routed(u, {**w, **{
            k: w[k][5:6] for k in ("experts_gate", "experts_up",
                                   "experts_down")}}, {**d, "held": (5, 1)})
                   + shared), atol=3e-5)


def test_the_sigmoid_router_is_the_published_rule():
    """Scores by the logistic function, the top k of score + bias, the
    weights the SCORES of the chosen renormalised and scaled: against
    the rule written out token by token in numpy."""
    n, k, h, scale = 8, 2, 128, 2.446
    layer = RoutedExperts(
        n_experts=n, d_model=h, d_ff=128, k=k, routed_scale=scale,
        dtype=jnp.float32, router_score="sigmoid", selection_bias=True)
    u = jax.random.normal(jax.random.PRNGKey(0), (1, 12, h))
    params = layer.init(jax.random.PRNGKey(1), u)["params"]
    assert not np.asarray(params["router_bias"]).any()
    params = {**params, "router_bias": 0.4 * jnp.sin(jnp.arange(8.0))}
    with jax.default_matmul_precision("highest"):
        got = np.asarray(layer.apply({"params": params}, u))[0]
    un = np.asarray(u[0], np.float64)
    w = {k_: np.asarray(v, np.float64) for k_, v in params.items()
         if k_.startswith("experts")}
    s = 1.0 / (1.0 + np.exp(-un @ np.asarray(params["router"]["kernel"],
                                             np.float64)))
    want = np.zeros_like(un)
    changed = 0
    for t in range(12):
        top = np.argsort(-(s[t] + np.asarray(params["router_bias"])))[:k]
        changed += set(top) != set(np.argsort(-s[t])[:k])
        for e in top:
            gate = un[t] @ w["experts_gate"][e]
            act = gate / (1.0 + np.exp(-gate)) * (un[t] @ w["experts_up"][e])
            want[t] += scale * s[t, e] / s[t, top].sum() * (
                act @ w["experts_down"][e])
    assert changed > 0, "the bias moved no choice: the test shows nothing"
    np.testing.assert_allclose(got, want, atol=2e-5)
