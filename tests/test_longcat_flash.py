"""``mixed_layer_lm`` as LongCat-Flash-Chat's layers: the
shortcut-connected expert layer (two latent-attention blocks and two
dense MLPs a layer, the routed experts' output joining a whole
attention + MLP later), latent attention with a rotated shared key, a
low-rank query and the two scales that go with the ranks, a stack whose
only cache is latents, and a router over real and zero-compute experts
that does not renormalise; against the plain reference
``benchmark/reference/longcat_flash.py`` at tiny widths on the CPU with
seeded weights (hidden 128, the grouped matmul's least; 2 layers, 8
heads, query rank 32, latent 32, rope 16, 16 experts + 8 zero, top 4).
A file of its own: the tier-1 command deals FILES to its workers."""

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
from test_kimi_linear import _served_logits  # the engine's contract on one row

ROOT = Path(__file__).resolve().parents[1]
SEED = 7


def _cfg():
    with open(ROOT / "benchmark/configs/_rehearsal"
              / "longcat-flash-chat-serve.json") as f:
        return json.load(f)


def _longcat():
    cfg = _cfg()
    arch = cells.architecture(cfg)
    model = {**cfg["model"], "dtype": "float32", "head_dtype": "float32"}
    return arch, arch.dims_of(cfg), model


IDS = np.asarray(jax.random.randint(jax.random.PRNGKey(0), (1, 50), 1, 512))


def _reference_logits(arch, d, ids):
    key = W.seed_key(SEED)
    top = arch.top_weights(key, d, jnp.float32)
    x = arch.embed(jnp.asarray(ids), top["emb"])
    pos = jnp.broadcast_to(jnp.arange(ids.shape[1])[None], ids.shape)
    for i, kind in enumerate(arch.layer_kinds(d)):
        x = arch.layer(x, arch.layer_weights(key, i, d, jnp.float32, kind),
                       pos, d, kind)
    return np.asarray(arch.logits(x, top, d))


@pytest.fixture(scope="module")
def served():
    """(the full forward's logits, the served path's): computed once,
    held to several references below."""
    arch, d, kw = _longcat()
    model = create_model(dict(kw))
    params = W.program_params(arch, SEED, d, jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)))
        return whole, _served_logits(model, params, IDS, n_prompt=21)


def test_a_stack_of_shortcut_layers_is_assembled_from_the_lists():
    arch, d, kw = _longcat()
    assert arch.layer_kinds(d) == ["shortcut", "shortcut"]
    model = create_model(dict(kw))
    # one entry a MIXER that reads context tokens: two a layer
    assert model.attention_windows() == (None,) * 4
    params = W.program_params(arch, SEED, d, jnp.float32)
    abstract = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    W.check_layout(params, abstract)
    assert set(params["layer_1"]) == {
        "attn", "RMSNorm_0", "moe", "gate", "up", "down",
        "attn_1", "RMSNorm_1", "gate_1", "up_1", "down_1"}
    for name in ("attn", "attn_1"):
        assert set(params["layer_0"][name]) == {
            "RMSNorm_0", "q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b",
            "out"}
    # the router is as wide as the real and the zero experts together,
    # its bias too; the weights are the held experts' alone
    moe = params["layer_0"]["moe"]
    assert set(moe) == {"router", "router_bias", "experts_gate",
                        "experts_up", "experts_down"}
    assert moe["router"]["kernel"].shape == (128, 16 + 8)
    assert moe["router_bias"].shape == (24,)
    assert moe["experts_gate"].shape == (4, 128, 128)
    # a slot's carry is latents and nothing else: two leaves a layer,
    # whole blocks and whole lanes, no leaf with a head axis
    for l_buf, slots in ((24, 32), (8449, 8704)):
        cache = jax.eval_shape(lambda: init_cache(model, 3, l_buf))
        for layer in ("layer_0", "layer_1"):
            assert {m: {k: v.shape for k, v in leaves.items()}
                    for m, leaves in cache[layer].items()} == {
                m: {"cached_latent": (3, slots, 128), "cache_index": ()}
                for m in ("attn", "attn_1")}


def test_the_reference_keeps_the_published_pairs_and_the_program_its_own():
    """``program_layer`` hands the program the rotating columns in the
    order its rotation pairs them: a permutation of 16 columns of
    ``q_b`` and ``kv_a``, nothing else moved."""
    arch, d, _ = _longcat()
    w = arch.layer_weights(W.seed_key(SEED), 0, d, jnp.float32, "shortcut")
    prog = arch.program_layer(w, "shortcut")
    order = np.asarray(arch.halves_first(16))
    np.testing.assert_array_equal(order, [*range(0, 16, 2), *range(1, 16, 2)])
    q_b, kv_a = prog["attn_1"]["q_b"]["kernel"], prog["attn_1"]["kv_a"]["kernel"]
    np.testing.assert_array_equal(q_b[..., :16], w["wq_b_1"][..., :16])
    np.testing.assert_array_equal(q_b[..., 16:], w["wq_b_1"][..., 16 + order])
    np.testing.assert_array_equal(kv_a[:, :32], w["w_kva_1"][:, :32])
    np.testing.assert_array_equal(kv_a[:, 32:], w["w_kva_1"][:, 32 + order])


def _wrong_layer(arch, wrong):
    """The reference's layer with the experts in another place."""
    def layer(x, w, positions, d, kind):
        norm = lambda x, name: arch.rms_norm(x, w[name], d["norm_eps"])  # noqa: E731
        mlp = lambda h, b: arch.swiglu(  # noqa: E731
            h, w[f"w_gate_{b}"], w[f"w_up_{b}"], w[f"w_down_{b}"])
        x1 = x + arch.mla(norm(x, "attn_norm_0"), w, "0", positions, d)
        h = norm(x1, "mlp_norm_0")
        x2 = x1 + mlp(h, "0")
        if wrong == "experts_join_before_a1":
            x2 = x2 + arch.routed(h, w, d)
        x3 = x2 + arch.mla(norm(x2, "attn_norm_1"), w, "1", positions, d)
        h3 = norm(x3, "mlp_norm_1")
        x4 = x3 + mlp(h3, "1")
        if wrong == "experts_fed_the_second_norm":
            x4 = x4 + arch.routed(h3, w, d)
        return x4
    return layer


@pytest.mark.parametrize("reference", [
    "as_published", "unrotated_k_pe", "no_kv_scale", "renormalised_gates",
    "no_zero_experts", "experts_fed_the_second_norm",
    "experts_join_before_a1"])
def test_the_latent_stack_serves_longcats_layers_and_no_other(
        served, reference, monkeypatch):
    """(a) the full forward and (b) a LEFT-padded prompt in chunks then
    single steps under a cursor, both latent caches of both layers,
    against ``reference/longcat_flash.py``: float32 agrees to 2e-4
    (absorbed products against expanded ones, a running softmax against
    a whole one, the program's rotary pairing against the published one
    on permuted columns); (d) the reference with one mechanism undone,
    or its experts in another place, does not agree."""
    arch, d, _ = _longcat()
    whole, got = served
    np.testing.assert_allclose(got, whole, atol=2e-4)
    rotate = arch.rotate
    patch = {
        # the shared key as it comes; each head's q_pe still turned
        "unrotated_k_pe": ("rotate", lambda x, positions, d: (
            x if x.ndim == 3 else rotate(x, positions, d))),
        "no_kv_scale": ("kv_scale", lambda d: 1.0),
        "renormalised_gates": ("gates", lambda chosen, d: chosen / jnp.sum(
            chosen, -1, keepdims=True) * d["routed_scale"]),
        "no_zero_experts": ("zero_part",
                            lambda u, weight, d: jnp.zeros_like(u)),
    }.get(reference)
    if patch:
        monkeypatch.setattr(arch, *patch)
    elif reference != "as_published":
        monkeypatch.setattr(arch, "layer", _wrong_layer(arch, reference))
    want = _reference_logits(arch, d, IDS)
    for name, logits in (("forward", whole), ("served", got)):
        err = np.abs(logits - want).max()
        if reference == "as_published":
            assert err < 2e-4, name
        else:
            assert err > 0.05, name


def test_two_requests_a_boundary_apart_are_the_references():
    """(b) through ``GenerationService``: B (3 chunks) is admitted while
    A decodes, a dispatch boundary later, so its chunks write both
    latent caches of every layer beside A's single-token steps, and its
    steps then run under another cursor than A's; teacher-forced through
    the float32 reference's full forward."""
    from benchmark.reference.check_serve import serve_readings
    from mlcomp_tpu.serve import GenerationService

    cfg = _cfg()
    arch = cells.architecture(cfg)
    d = arch.dims_of(cfg)
    model = create_model({**cfg["model"], "dtype": "float32",
                          "head_dtype": "float32"})
    # the values the reference regenerates: drawn in bfloat16
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        W.program_params(arch, 11, d, jnp.bfloat16))
    svc = GenerationService(
        model, {"params": params}, seed=1, metrics_history_interval=None,
        batcher="continuous", batch_sizes=(2,), prompt_buckets=(64,),
        max_new_buckets=(24,), prefill_chunk=16, steps_per_dispatch=4)
    try:
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, 512, size=n).tolist() for n in (13, 45)]
        a = svc.submit(prompts[0], 24, temperature=0.0, logprobs=True)
        while not svc.stats()["engine"]["steps"]:   # A decodes
            pass
        b = svc.submit(prompts[1], 10, temperature=0.0, logprobs=True)
        outs = [a.result(timeout=600), b.result(timeout=600)]
        eng = svc.stats()["engine"]
    finally:
        svc.close()
    assert [len(o["ids"]) for o in outs] == [24, 10]
    samples = [{"ids": p, "out": o["ids"], "logprobs": o["logprobs"]}
               for p, o in zip(prompts, outs)]
    with jax.default_matmul_precision("highest"):
        got = serve_readings(cfg, 11, samples, 64 + 24)
    assert got["tokens_compared"] == 34
    assert got["max_logit_gap"] < 1e-3
    assert got["mean_abs_logprob_err"] < 1e-3
    # every chunk and step of the four attention blocks was counted
    lat = eng["latent"]
    assert lat["chunk_tokens"] == 4 * sum(len(p) for p in prompts)
    assert lat["tokens_attended"] > 0
    moe = eng["moe"]
    assert 0 < moe["zero_assignments"] < moe["assignments"]
    assert moe["zero_assignments"] == sum(
        c["zero_assignments"] for c in moe["by_class"].values())
    # two mixers a layer read the context: the share counts four
    att = eng["attention"]
    assert att["kv_tokens_attended"] == att["kv_tokens_live"] > 0


def _share_params(w, first, count):
    return {
        "router": {"kernel": w["router"]},
        "router_bias": w["router_bias"],
        **{f"experts_{n}": w[f"experts_{n}"][first:first + count]
           for n in ("gate", "up", "down")},
    }


def test_the_shares_and_the_zero_experts_once_are_the_whole_layer():
    """(c) guide section 4's share test at LongCat's cut: four chips
    share a layer, each holds a quarter of the 16 real experts; what the
    four compute, with the zero experts' part (which all compute alike)
    counted once, is the uncut reference layer's ``E(h)``: softmax over
    all 24 outputs, the selection bias, the top four, weights not
    renormalised."""
    arch, d, _ = _longcat()
    h, f = d["hidden"], d["expert_width"]
    assert (d["experts"], d["zero_experts"], d["top_k"]) == (16, 8, 4)
    uncut = {**d, "held": (0, 16)}
    w = arch.layer_weights(W.seed_key(3), 1, uncut, jnp.float32, "shortcut")
    # a bias large enough to change choices, as the drawn one is small
    w["router_bias"] = 0.02 * jnp.cos(jnp.arange(24.0))
    assert w["experts_gate"].shape == (16, h, f)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 24, h), jnp.float32)
    weight = arch.route(u, w, uncut)
    # un-renormalised: the four weights of a token do not sum to the scale
    sums = np.asarray(weight.sum(-1))
    assert np.all((weight > 0).sum(-1) == 4)
    assert np.abs(sums - d["routed_scale"]).min() > 1.0
    unbiased = arch.route(u, {**w, "router_bias": 0 * w["router_bias"]}, uncut)
    assert np.abs(np.asarray(weight - unbiased)).max() > 0.1
    zero = arch.zero_part(u, weight, uncut)
    assert np.abs(np.asarray(zero)).max() > 0.05
    whole = arch.routed(u, w, uncut)

    def share(first, count=4):
        layer = RoutedExperts(
            n_experts=16, d_model=h, d_ff=f, k=d["top_k"],
            experts_held=(first, count), routed_scale=d["routed_scale"],
            dtype=jnp.float32, selection_bias=True, zero_experts=8,
            renormalise=False)
        with jax.default_matmul_precision("highest"):
            return layer.apply(
                {"params": _share_params(w, first, count)}, u,
                mutable=["counters"])

    parts = [share(first) for first in range(0, 16, 4)]
    np.testing.assert_allclose(
        np.asarray(sum(y for y, _ in parts) - 3 * zero), np.asarray(whole),
        atol=3e-5)
    # every share counted the same zero choices: all 4 a token routed,
    # those past the 16 real experts going nowhere near the layout
    chosen_zero = float((np.asarray(weight)[..., 16:] > 0).sum())
    for _, upd in parts:
        counts = np.asarray(upd["counters"]["moe"])
        assert counts[0] == 2 * 24 * 4
        assert counts[11] == counts[12] == chosen_zero > 0
    held = sum(float(np.asarray(upd["counters"]["moe"])[1])
               for _, upd in parts)
    assert held + chosen_zero == 2 * 24 * 4
    # and a share alone is the reference's share
    np.testing.assert_allclose(
        np.asarray(parts[2][0]),
        np.asarray(arch.routed(u, {**w, **{
            k: w[k][8:12] for k in ("experts_gate", "experts_up",
                                    "experts_down")}}, {**d, "held": (8, 4)})),
        atol=3e-5)


def test_the_row_tile_divides_by_the_routers_whole_width(monkeypatch):
    """The rows a real expert can expect are tokens x k over ALL the
    router's outputs, the zero experts with them."""
    from mlcomp_tpu.ops.pallas import grouped_matmul as gm

    seen = []
    auto = gm.auto_row_tile
    monkeypatch.setattr(gm, "auto_row_tile",
                        lambda *a: seen.append(a) or auto(*a))
    layer = RoutedExperts(n_experts=16, d_model=128, d_ff=128, k=4,
                          experts_held=(0, 4), dtype=jnp.float32,
                          zero_experts=8, renormalise=False)
    u = jnp.zeros((1, 96, 128))
    jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), u))
    assert seen == [(96, 4, 24)] and auto(96, 4, 24) == 16
    assert auto(2048, 12, 768) == 32 and auto(24, 12, 768) == 16
