"""Prefix KV cache (mlcomp_tpu/cache): trie semantics (longest-prefix
match, LRU eviction, ref-count pinning, edge splits), end-to-end
engine equality — cache-hit generation must emit EXACTLY the tokens
cold prefill emits, bf16 and kv8 cache layouts — and the serving
surface (per-request cache_hit_tokens, /cache/stats, warmup
isolation)."""

import queue
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlcomp_tpu.cache import PrefixIndex, PrefixKVCache
from mlcomp_tpu.cache.kv_store import KVBlock
from mlcomp_tpu.engine import DecodeEngine
from mlcomp_tpu.models import create_model
from mlcomp_tpu.models.generation import generate
from mlcomp_tpu.serve import GenerationService
from mlcomp_tpu.train.state import init_model


def _block(ids):
    """Self-checking block: the payload IS the ids, so slice/split
    bookkeeping errors surface as token mismatches."""
    return KVBlock(
        {"ids": np.asarray(list(ids), np.int64)[None]}, {"ids": 1},
        len(ids),
    )


def _lease_ids(lease):
    out = []
    for block, take in lease.segments:
        out.extend(block.arrays["ids"][0, :take].tolist())
    return out


# ----------------------------------------------------------- trie unit


def test_trie_longest_prefix_match_and_split():
    idx = PrefixIndex(1 << 20)
    assert idx.lookup([1, 2, 3]) is None
    idx.insert([1, 2, 3, 4], _block([1, 2, 3, 4]))
    with idx.lookup([1, 2, 3, 9]) as lease:
        assert lease.tokens == 3 and _lease_ids(lease) == [1, 2, 3]
    # divergence mid-edge splits the node; both arms stay reachable
    idx.insert([1, 2, 7, 8], _block([1, 2, 7, 8]))
    idx.check_invariants()
    with idx.lookup([1, 2, 7, 8, 5]) as lease:
        assert lease.tokens == 4 and _lease_ids(lease) == [1, 2, 7, 8]
    with idx.lookup([1, 2, 3, 4]) as lease:
        assert lease.tokens == 4 and _lease_ids(lease) == [1, 2, 3, 4]
    # dedup: re-inserting an existing prefix stores nothing new
    assert idx.insert([1, 2, 3], _block([1, 2, 3])) == 0
    # offset insert: block covers only the new suffix
    assert idx.insert([1, 2, 3, 4, 5, 6], _block([5, 6]), offset=4) == 2
    with idx.lookup([1, 2, 3, 4, 5, 6]) as lease:
        assert _lease_ids(lease) == [1, 2, 3, 4, 5, 6]


def test_trie_lru_eviction_under_byte_budget():
    # payload int64 -> 8 bytes/token; budget of 7 tokens
    idx = PrefixIndex(7 * 8)
    idx.insert([1, 2, 3], _block([1, 2, 3]))
    idx.insert([5, 6, 7], _block([5, 6, 7]))
    idx.lookup([1, 2, 3]).release()          # [5,6,7] is now LRU
    idx.insert([8, 9], _block([8, 9]))       # 8 tokens > 7 -> evict LRU
    idx.check_invariants()
    st = idx.stats()
    assert st["evictions"] == 1 and st["bytes"] <= 7 * 8
    assert idx.lookup([5, 6, 7]) is None     # the LRU victim
    assert idx.lookup([1, 2, 3]).tokens == 3


def test_trie_refcount_pins_against_eviction():
    idx = PrefixIndex(6 * 8)
    idx.insert([1, 2, 3], _block([1, 2, 3]))
    lease = idx.lookup([1, 2, 3])
    # massive pressure: everything unpinned must go before the lease's
    # nodes; the pinned data stays intact even while over budget
    idx.insert([7] * 6, _block([7] * 6))
    idx.check_invariants()
    assert _lease_ids(lease) == [1, 2, 3]
    with idx.lookup([1, 2, 3]) as again:
        assert again.tokens == 3
    lease.release()
    lease.release()  # idempotent
    idx.evict_to_budget()
    assert idx.stats()["pinned_nodes"] == 0
    assert idx.stats()["bytes"] <= 6 * 8


def test_trie_concurrent_eviction_race():
    """Racing lookups/inserts/evictions under a tiny budget: pinned
    leases keep their bytes, invariants hold throughout, refcounts
    return to zero."""
    idx = PrefixIndex(40 * 8)
    errs = []

    def worker(seed):
        rs = np.random.RandomState(seed)
        try:
            for _ in range(200):
                ids = rs.randint(1, 5, rs.randint(1, 12)).tolist()
                if rs.rand() < 0.5:
                    idx.insert(ids, _block(ids))
                else:
                    lease = idx.lookup(ids)
                    if lease is not None:
                        want = ids[: lease.tokens]
                        idx.evict_to_budget()  # pressure WHILE pinned
                        assert _lease_ids(lease) == want
                        lease.release()
                idx.check_invariants()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    if errs:
        raise errs[0]
    idx.check_invariants()
    assert idx.stats()["pinned_nodes"] == 0
    idx.evict_to_budget()
    assert idx.stats()["bytes"] <= 40 * 8


# ------------------------------------------------------- engine e2e


def _model_and_params(kv_quant=False, seed=0):
    model = create_model({
        "name": "transformer_lm", "vocab_size": 64, "hidden": 64,
        "layers": 2, "heads": 2, "mlp_dim": 128, "dtype": "float32",
        "kv_quant": kv_quant,
    })
    prompt = jnp.asarray(np.random.RandomState(seed).randint(1, 64, (1, 8)))
    params, _ = init_model(model, {"x": prompt}, jax.random.PRNGKey(seed))
    return model, params


def _reference(model, params, ids, n_new, bucket=32):
    prompt = np.full((1, bucket), 0, np.int32)
    mask = np.zeros((1, bucket), bool)
    prompt[0, bucket - len(ids):] = ids
    mask[0, bucket - len(ids):] = True
    out = generate(
        model, {"params": params}, jnp.asarray(prompt), n_new,
        prompt_mask=jnp.asarray(mask),
    )
    return np.asarray(out)[0, bucket:].tolist()


@pytest.mark.parametrize("kv_quant", [False, True])
def test_engine_cache_hit_outputs_equal_cold(kv_quant):
    """The acceptance bar: token-level output equality between
    cache-hit and uncached generation, for both cache layouts —
    identical resubmit, shared-prefix different-suffix, and a
    different-LENGTH sharer (different left-pad offset)."""
    model, params = _model_and_params(kv_quant)
    eng = DecodeEngine(
        model, {"params": params}, slots=2, prompt_buckets=(32,),
        max_new_cap=8, prefill_chunk=8,
        prefix_cache=PrefixKVCache(max_bytes=64 << 20),
    )
    try:
        rs = np.random.RandomState(5)
        ids = rs.randint(1, 64, 28).tolist()
        cold = eng.submit(ids, 6).result(timeout=300)
        assert cold["cache_hit_tokens"] == 0
        eng.prefix_cache.flush()  # captures land on a background worker
        hot = eng.submit(ids, 6).result(timeout=300)
        # 28 real tokens, pad 4, chunk 8: match capped at 27 ->
        # boundary chunk 3 -> 3*8-4 = 20 tokens skipped
        assert hot["cache_hit_tokens"] == 20
        assert hot["ids"] == cold["ids"] == _reference(
            model, params, ids, 6
        )
        # shared 20-token prefix, fresh suffix, same length
        ids2 = ids[:20] + rs.randint(1, 64, 8).tolist()
        r2 = eng.submit(ids2, 6).result(timeout=300)
        assert r2["cache_hit_tokens"] > 0
        assert r2["ids"] == _reference(model, params, ids2, 6)
        # different length (start_pad 8 vs 4): rows transplant by token
        # index, not slot
        ids3 = ids[:20] + rs.randint(1, 64, 4).tolist()
        r3 = eng.submit(ids3, 6).result(timeout=300)
        assert r3["cache_hit_tokens"] > 0
        assert r3["ids"] == _reference(model, params, ids3, 6)
        eng.prefix_cache.flush()
        st = eng.stats()["prefix_cache"]
        assert st["hits"] == 3 and st["misses"] == 1
        assert st["used_hit_tokens"] > 0 and st["bytes"] > 0
    finally:
        eng.close()


def test_engine_cache_budget_eviction_keeps_serving():
    """A budget too small for the traffic evicts instead of growing —
    and requests keep producing exact outputs (hit or miss)."""
    model, params = _model_and_params()
    # room for roughly one 28-token prompt's rows (~57 KB), not several
    eng = DecodeEngine(
        model, {"params": params}, slots=2, prompt_buckets=(32,),
        max_new_cap=8, prefill_chunk=8,
        prefix_cache=PrefixKVCache(max_bytes=60_000),
    )
    try:
        rs = np.random.RandomState(6)
        for _ in range(4):
            ids = rs.randint(1, 64, 28).tolist()
            got = eng.submit(ids, 4).result(timeout=300)
            assert got["ids"] == _reference(model, params, ids, 4)
            eng.prefix_cache.flush()
        st = eng.stats()["prefix_cache"]
        assert st["evictions"] > 0
        assert st["bytes"] <= 60_000
    finally:
        eng.close()


def test_engine_warns_when_no_bucket_can_hit():
    """Hits are chunk-granular: a bucket that prefills as one chunk
    can never hit — the constructor says so instead of serving a
    silently zero-hit cache."""
    import warnings

    model, params = _model_and_params()
    with pytest.warns(UserWarning, match="impossible"):
        eng = DecodeEngine(
            model, {"params": params}, slots=2, prompt_buckets=(32,),
            max_new_cap=8,  # default prefill_chunk 256 > bucket 32
            prefix_cache=PrefixKVCache(max_bytes=1 << 20),
        )
    eng.close()
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # divisible buckets stay silent
        eng = DecodeEngine(
            model, {"params": params}, slots=2, prompt_buckets=(32,),
            max_new_cap=8, prefill_chunk=8,
            prefix_cache=PrefixKVCache(max_bytes=1 << 20),
        )
    eng.close()


def test_engine_mesh_refuses_prefix_cache():
    model, params = _model_and_params()

    class FakeMesh:  # the check precedes any mesh use
        pass

    with pytest.raises(ValueError, match="single-chip"):
        DecodeEngine(
            model, {"params": params}, slots=2, prompt_buckets=(32,),
            max_new_cap=8, mesh=FakeMesh(),
            prefix_cache=PrefixKVCache(max_bytes=1 << 20),
        )


# ------------------------------------------------------- service/HTTP


def test_service_prefix_cache_http_stats_and_hit_tokens():
    """GenerationService(prefix_cache=True): warmup stays out of the
    cache, responses carry cache_hit_tokens, and GET /cache/stats
    serves the counters (404 when the cache is off)."""
    import json
    import socket
    import urllib.error
    import urllib.request

    from mlcomp_tpu.serve import serve_http

    model, params = _model_and_params()
    svc = GenerationService(
        model, {"params": params}, batch_sizes=(1, 2),
        prompt_buckets=(32,), max_new_buckets=(4, 8),
        prefill_chunk=8, prefix_cache=True,
        prefix_cache_bytes=64 << 20,
    )
    assert svc.engine is not None and svc.engine.prefix_cache is not None
    svc.warmup()
    assert svc.cache_stats()["inserted_tokens"] == 0  # warmup excluded

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    threading.Thread(
        target=serve_http, args=(svc,), kwargs={"port": port}, daemon=True,
    ).start()

    import time as _t

    ids = np.random.RandomState(2).randint(1, 64, 28).tolist()
    body = json.dumps({"prompt": ids, "max_new_tokens": 4}).encode()

    def post():
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate", data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req) as r:
            return json.loads(r.read())

    for _ in range(50):
        try:
            cold = post()
            break
        except OSError:
            _t.sleep(0.1)
    else:
        raise AssertionError("server never came up")
    svc.engine.prefix_cache.flush()  # async capture -> deterministic hit
    hot = post()
    assert cold["cache_hit_tokens"] == 0
    assert hot["cache_hit_tokens"] > 0
    assert hot["ids"] == cold["ids"]
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/cache/stats"
    ) as r:
        stats = json.loads(r.read())
    assert stats["hits"] >= 1 and stats["bytes"] > 0
    svc.close()

    # cache off -> /cache/stats is 404 (and cache_stats() is None)
    svc2 = GenerationService(
        model, {"params": params}, batch_sizes=(1,),
        prompt_buckets=(32,), max_new_buckets=(4,),
    )
    assert svc2.cache_stats() is None
    svc2.close()


def test_service_prefix_cache_validation():
    """Host row inserts don't compose with a sharded cache: refused at
    construction, before an engine exists."""
    from jax.sharding import Mesh

    model, params = _model_and_params()
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("dp",))
    with pytest.raises(ValueError, match="single-chip"):
        GenerationService(
            model, {"params": params}, mesh=mesh,
            batch_sizes=(2,), prompt_buckets=(32,),
            max_new_buckets=(4,), prefix_cache=True,
        )
