"""Power retention (``models/retention.py``, ``ops/pallas/retention.py``):
the three forms of the one function agree with each other at float32 on
seeded weights and tiny widths, the kernel in interpret mode is the
chunk form of one token, a served window through ``GenerationService``
(chunked admission, a slot used twice, a row retiring inside a
dispatch) is the reference's, and the engine refuses by name what a
cache of state cannot do."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from benchmark import weights as W
from mlcomp_tpu.models import create_model
from mlcomp_tpu.models.retention import (
    COUNTS,
    PowerRetention,
    _causal_part,
    _state_part,
    expand_slab,
)
from mlcomp_tpu.models.transformer import RopeSpec
from mlcomp_tpu.ops.pallas.retention import (
    expanded_width,
    retention_step,
    slab_weights,
    slabs,
    state_bytes_moved,
)

ROOT = Path(__file__).resolve().parents[1]
HIDDEN, HEADS, KV, DH = 64, 4, 2, 16


def _layer(dtype=jnp.float32):
    return PowerRetention(HIDDEN, HEADS, KV, DH, dtype,
                          rope=RopeSpec(base=1e6))


@pytest.fixture(scope="module")
def seeded():
    """(params, inputs (3, 24, hidden), positions, the fresh form's
    output): gates of ~0.95 to ~0.9997, the bias the module sets."""
    layer = _layer()
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 24, HIDDEN))
    pos = jnp.broadcast_to(jnp.arange(24), (3, 24))
    params = layer.init(jax.random.PRNGKey(1), x, pos)["params"]
    np.testing.assert_allclose(params["gate"]["bias"], [3.0, 8.0])
    # learned norm vectors that are not all ones
    params = {**params, "q_norm": 1.0 + 0.3 * jnp.cos(jnp.arange(DH)),
              "k_norm": 1.0 - 0.2 * jnp.sin(jnp.arange(DH))}
    return params, x, pos, np.asarray(layer.apply({"params": params}, x, pos))


def _zero_cache(layer, b):
    shapes = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), jnp.zeros((b, 4, HIDDEN)),
        jnp.zeros((b, 4), jnp.int32), decode=True))["cache"]
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


@pytest.mark.parametrize("dh", [16, 128])
def test_the_slabs_are_the_symmetric_second_power(dh):
    q, k = jax.random.normal(jax.random.PRNGKey(dh), (2, 7, dh))
    assert slabs(dh) == dh // 2 + 1
    assert expanded_width(dh) == slabs(dh) * dh >= dh * (dh + 1) // 2
    c = slab_weights(dh)
    inner = sum((expand_slab(q, r, c[r]) * expand_slab(k, r, c[r])).sum(-1)
                for r in range(slabs(dh)))
    np.testing.assert_allclose(inner, (q * k).sum(-1) ** 2, rtol=2e-5,
                               atol=1e-3)


@pytest.mark.parametrize("cuts", [(7, 15), (1, 2, 23), (8, 16)],
                         ids=["3_chunks", "single_tokens_first", "even"])
def test_chunks_that_carry_the_state_are_the_fresh_form(seeded, cuts):
    params, x, pos, fresh = seeded
    layer = _layer()
    cache = _zero_cache(layer, 3)
    out, tokens = [], 0.0
    for lo, hi in zip((0,) + cuts, cuts + (24,)):
        y, upd = layer.apply(
            {"params": params, "cache": cache}, x[:, lo:hi], pos[:, lo:hi],
            decode=True, mutable=["cache", "counters"])
        cache = upd["cache"]
        out.append(np.asarray(y))
        counts = dict(zip(
            COUNTS.names, np.asarray(upd["counters"]["retention"])))
        assert counts["layer_calls"] == 1 and counts["state_rows"] == 0
        tokens += counts["chunk_tokens"]
    assert tokens == 3 * 24 and int(cache["cache_index"]) == 24
    np.testing.assert_allclose(np.concatenate(out, 1), fresh, atol=2e-5)


def test_left_pads_inside_a_chunk_add_nothing_and_decay_nothing(seeded):
    """A prompt of 19 left-padded into a bucket of 24, in chunks of 8:
    five pads and three tokens share the first chunk."""
    params, x, pos, fresh = seeded
    layer = _layer()
    pad, n = 5, 19
    xs = jnp.concatenate([jnp.ones((3, pad, HIDDEN)) * 9.0, x[:, :n]], 1)
    ps = jnp.maximum(jnp.arange(24) - pad, 0)[None].repeat(3, 0)
    kv_mask = jnp.broadcast_to(jnp.arange(40) >= pad, (3, 40))
    cache = _zero_cache(layer, 3)
    out = []
    for lo in (0, 8, 16):
        y, upd = layer.apply(
            {"params": params, "cache": cache}, xs[:, lo:lo + 8],
            ps[:, lo:lo + 8], decode=True, kv_mask=kv_mask,
            mutable=["cache", "counters"])
        cache = upd["cache"]
        out.append(np.asarray(y))
    assert float(upd["counters"]["retention"][2]) == 3 * 8
    got = np.concatenate(out, 1)[:, pad:]
    np.testing.assert_allclose(got, fresh[:, :n], atol=2e-5)
    # the same through the fresh form's own mask
    whole = layer.apply({"params": params}, xs, ps, kv_mask=kv_mask)
    np.testing.assert_allclose(np.asarray(whole)[:, pad:], fresh[:, :n],
                               atol=2e-5)


def test_single_token_steps_under_cursors_are_the_fresh_form(seeded):
    """A chunk of 10, then the kernel (interpret mode) a token a row at
    per-row cursors; row 1 holds no request from step 14 on: its state
    is neither read nor written."""
    params, x, pos, fresh = seeded
    layer = _layer()
    cache = _zero_cache(layer, 3)
    y, upd = layer.apply({"params": params, "cache": cache}, x[:, :10],
                         pos[:, :10], decode=True,
                         mutable=["cache", "counters"])
    cache, out = upd["cache"], [np.asarray(y)]
    for t in range(10, 24):
        live = np.array([True, t < 14, True])
        kv_mask = jnp.asarray(np.broadcast_to(live[:, None], (3, 40)))
        y, upd = layer.apply(
            {"params": params, "cache": cache}, x[:, t:t + 1],
            pos[:, t:t + 1], decode=True, kv_mask=kv_mask,
            cache_cursor=jnp.full((3,), t), mutable=["cache", "counters"])
        for leaf in ("state", "norm"):
            assert np.array_equal(np.asarray(upd["cache"][leaf])[~live],
                                  np.asarray(cache[leaf])[~live])
        counts = dict(zip(
            COUNTS.names, np.asarray(upd["counters"]["retention"])))
        assert counts["state_rows"] == live.sum()
        assert counts["state_bytes"] == state_bytes_moved(
            int(live.sum()), KV, DH)
        assert counts["chunk_tokens"] == 0 and counts["layer_calls"] == 1
        assert int(upd["cache"]["cache_index"]) == 10  # the engine's cursors
        cache = upd["cache"]
        out.append(np.asarray(y))
    got = np.concatenate(out, 1)
    np.testing.assert_allclose(got[[0, 2]], fresh[[0, 2]], atol=2e-5)
    np.testing.assert_allclose(got[1, :14], fresh[1, :14], atol=2e-5)
    # a row without a request gets the residual alone
    np.testing.assert_array_equal(got[1, 14:], np.asarray(x)[1, 14:])


@pytest.mark.parametrize("live", [(True, True, True, True),
                                  (False, True, False, True),
                                  (False, False, False, False)],
                         ids=["all", "two_of_four", "none"])
def test_the_kernel_is_the_chunk_form_of_one_token(live):
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    g = HEADS // KV
    q = jax.random.normal(ks[0], (4, KV, g, DH))
    k = jax.random.normal(ks[1], (4, KV, DH))
    v = jax.random.normal(ks[2], (4, KV, DH))
    log_g = -jax.random.uniform(ks[3], (4, KV))
    state = jax.random.normal(ks[4], (4, KV, expanded_width(DH), DH))
    norm = jnp.abs(jax.random.normal(ks[5], (4, KV, expanded_width(DH))))
    live = jnp.asarray(live)
    out, s1, z1 = retention_step(q, k, v, log_g, live, state, norm,
                                 eps=1e-6, product_dtype=jnp.float32,
                                 interpret=True)
    # the XLA form: q (B, 1, N, G, dh) against the carried state
    r = slabs(DH)
    cum = log_g[:, None]
    num, den = _causal_part(q[:, None], k[:, None], v[:, None], cum,
                            jnp.float32)
    num_s, den_s, s2, z2 = _state_part(
        q[:, None], k[:, None], v[:, None], cum,
        state.reshape(4, KV, r, DH, DH), norm.reshape(4, KV, r, DH),
        jnp.float32)
    want = ((num + num_s) / (den + den_s + 1e-6)[..., None])[:, 0]
    lv = np.asarray(live)
    np.testing.assert_allclose(np.asarray(out)[lv], np.asarray(want)[lv],
                               rtol=2e-4, atol=2e-5)
    assert not np.asarray(out)[~lv].any()
    for got, new, old in ((s1, s2.reshape(state.shape), state),
                          (z1, z2.reshape(norm.shape), norm)):
        np.testing.assert_allclose(np.asarray(got)[lv], np.asarray(new)[lv],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(got)[~lv],
                                      np.asarray(old)[~lv])


def test_the_walk_moves_a_slots_state_once_each_way():
    """The cell's geometry: 8 KV heads of 128, 8,320 entries a head."""
    a_slot = 8 * 8320 * (128 + 1) * 4
    assert state_bytes_moved(1, 8, 128) == 2 * a_slot
    assert state_bytes_moved(20, 8, 128) == 20 * 2 * a_slot
    assert abs(a_slot / 1e6 - 34.35) < 0.01


# ---- served: create_model -> GenerationService -> DecodeEngine ----

def _rehearsal():
    with open(ROOT / "benchmark/configs/_rehearsal/brumby-14b-serve.json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def service():
    """The rehearsal configuration at float32 on two slots, chunks of
    16 tokens through the one lane, K = 4."""
    from mlcomp_tpu.serve import GenerationService

    cfg = _rehearsal()
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
        max_new_buckets=(16,), prefill_chunk=16, steps_per_dispatch=4)
    yield svc, cfg
    svc.close()


def test_a_served_window_is_the_references(service):
    """Three requests on two slots: B (prompt of 3 chunks, 14 tokens)
    runs beside A (6 tokens: it retires inside its second dispatch),
    then C takes A's slot while B still decodes, so a state left over
    from A, or one written by a row that holds no request, would show.
    Teacher-forced through the float32 reference."""
    from benchmark.reference.check_serve import serve_readings

    svc, cfg = service
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 512, size=n).tolist() for n in (21, 45, 37)]

    def ask(ids, n_new):
        return svc.submit(ids, n_new, temperature=0.0, logprobs=True)

    a, b = ask(prompts[0], 6), ask(prompts[1], 14)
    out_a = a.result(timeout=600)
    c = ask(prompts[2], 13)
    outs = [out_a, b.result(timeout=600), c.result(timeout=600)]
    assert [len(o["ids"]) for o in outs] == [6, 14, 13]
    samples = [{"ids": p, "out": o["ids"], "logprobs": o["logprobs"]}
               for p, o in zip(prompts, outs)]
    got = serve_readings(cfg, 11, samples, 64 + 16)
    assert got["tokens_compared"] == 33
    assert got["max_logit_gap"] < 1e-3
    assert got["mean_abs_logprob_err"] < 1e-3
    eng = svc.stats()["engine"]
    ret = eng["retention"]
    # every chunk and every step of both layers was counted
    assert ret["chunk_tokens"] == 2 * sum(len(p) for p in prompts)
    assert ret["state_bytes"] == state_bytes_moved(ret["state_rows"], 2, 16)
    assert 0 < ret["state_rows_over_issued"] <= 1.0
    # no attention layer: the shares that divide by context tokens read None
    att = eng["attention"]
    assert att["kv_tokens_live"] == 0
    assert att["kv_tokens_attended_share"] is None


@pytest.mark.parametrize("asked,named", [
    ({"kv_layout": "paged", "kv_page_tokens": 16}, "kv_layout='paged'"),
    ({"prefix_cache": True}, "prefix_cache"),
    ({"prefill_only": True, "kv_page_tokens": 16}, "prefill_only"),
], ids=["paged", "prefix_cache", "kv_export"])
def test_a_cache_of_state_refuses_what_moves_kv_by_pages_or_prefix(
        asked, named):
    from mlcomp_tpu.cache import PrefixKVCache
    from mlcomp_tpu.engine import DecodeEngine

    cfg = _rehearsal()
    model = create_model(dict(cfg["model"]))
    abstract = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), abstract)
    if asked.get("prefix_cache"):
        asked = {"prefix_cache": PrefixKVCache(1 << 20)}
    with pytest.raises(ValueError, match=named) as err:
        DecodeEngine(model, {"params": params}, slots=2,
                     prompt_buckets=(32,), max_new_cap=16, prefill_chunk=16,
                     **asked)
    assert "per-slot state ['norm', 'state']" in str(err.value)
