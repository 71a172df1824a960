"""Serving resilience semantics: per-request deadlines and
cancellation (queued, in-flight, mid-prefill), admission-control
backpressure (429 + Retry-After), pipeline-depth equality for
survivors when a neighbor is cancelled, and prefix-cache fault
containment (degraded bypass returns exact tokens).  The end-to-end
fault/recovery story (watchdog restarts, 503 health) lives in
tools/chaoscheck.py, wired tier-1 by test_chaoscheck.py."""

import json
import queue
import threading
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlcomp_tpu.engine import (
    DeadlineExceeded,
    DecodeEngine,
    RequestCancelled,
)
from mlcomp_tpu.models import create_model
from mlcomp_tpu.models.generation import generate
from mlcomp_tpu.serve import BackpressureError, GenerationService
from mlcomp_tpu.train.state import init_model
from mlcomp_tpu.utils import faults


@pytest.fixture(autouse=True)
def _disarm():
    yield
    faults.disarm_all()


def _model_and_params(seed=0):
    model = create_model({
        "name": "transformer_lm", "vocab_size": 64, "hidden": 32,
        "layers": 1, "heads": 2, "mlp_dim": 64, "dtype": "float32",
    })
    prompt = jnp.asarray(np.random.RandomState(seed).randint(1, 64, (1, 8)))
    params, _ = init_model(model, {"x": prompt}, jax.random.PRNGKey(seed))
    return model, params


def _reference(model, params, ids, n_new, bucket=16):
    prompt = np.full((1, bucket), 0, np.int32)
    mask = np.zeros((1, bucket), bool)
    prompt[0, bucket - len(ids):] = ids
    mask[0, bucket - len(ids):] = True
    out = generate(
        model, {"params": params}, jnp.asarray(prompt), n_new,
        prompt_mask=jnp.asarray(mask),
    )
    return np.asarray(out)[0, bucket:].tolist()


def test_deadline_expiry_mid_decode_frees_slot_and_pins():
    """A request whose deadline lands mid-decode fails with
    DeadlineExceeded at a dispatch boundary, its slot frees for the
    next admission, and any prefix-cache pins are released."""
    model, params = _model_and_params()
    eng = DecodeEngine(model, {"params": params}, slots=2,
                       prompt_buckets=(16,), max_new_cap=32,
                       steps_per_dispatch=1)
    try:
        base = eng.submit([3, 14, 15], 6).result(timeout=300)["ids"]  # warm
        # slow every resolve so a 32-token budget cannot finish within
        # the deadline — expiry is guaranteed mid-decode, not flaky
        faults.arm("engine.resolve", flavor="sleep", times=-1,
                   seconds=0.02)
        q: "queue.Queue" = queue.Queue()
        fut = eng.submit([3, 14, 15], 32, deadline_s=0.15, stream=q)
        with pytest.raises(DeadlineExceeded):
            fut.result(timeout=60)
        assert fut.exception().status == "deadline_exceeded"
        # the stream was terminated too
        items = []
        while True:
            item = q.get(timeout=10)
            if item is None:
                break
            items.append(item)
        assert len(items) < 32  # it really died mid-decode
        faults.disarm_all()
        st = eng.stats()
        assert st["deadline_exceeded"] == 1
        assert st["active_slots"] == 0  # the slot is free again
        # and the engine still produces exact tokens afterwards
        assert eng.submit([3, 14, 15], 6).result(timeout=300)["ids"] == base
    finally:
        eng.close()


def test_deadline_frees_prefix_cache_state():
    """Deadline retirement with a prefix cache: no outstanding leases
    or pinned nodes survive the retirement."""
    model, params = _model_and_params()
    from mlcomp_tpu.cache import PrefixKVCache

    pc = PrefixKVCache(max_bytes=1 << 28)
    eng = DecodeEngine(model, {"params": params}, slots=2,
                       prompt_buckets=(16,), max_new_cap=32,
                       prefill_chunk=8, prefix_cache=pc,
                       steps_per_dispatch=1)
    try:
        shared = [9, 10, 11, 12, 13, 14, 15, 16, 17]
        eng.submit(shared + [1], 4).result(timeout=300)
        pc.flush()
        faults.arm("engine.resolve", flavor="sleep", times=-1,
                   seconds=0.02)
        # this request LEASES the cached prefix on admission, then dies
        fut = eng.submit(shared + [2], 32, deadline_s=0.15)
        with pytest.raises(DeadlineExceeded):
            fut.result(timeout=60)
        faults.disarm_all()
        pc.flush()
        cs = pc.stats()
        assert cs["outstanding_leases"] == 0, cs
        assert cs["pinned_nodes"] == 0, cs
        pc.index.check_invariants()
    finally:
        eng.close()


def test_cancel_queued_vs_inflight():
    """Cancelling a QUEUED request fails it without it ever taking a
    slot; cancelling an IN-FLIGHT request retires the row at the next
    boundary and frees its slot for the queued successor."""
    model, params = _model_and_params()
    eng = DecodeEngine(model, {"params": params}, slots=1,
                       prompt_buckets=(16,), max_new_cap=32,
                       steps_per_dispatch=1)
    try:
        qa: "queue.Queue" = queue.Queue()
        fa = eng.submit([5, 6, 7], 32, stream=qa)
        qa.get(timeout=300)  # A holds the one slot, decoding
        # hold A in flight for certain: its other 31 one-step
        # dispatches take a quarter of a second each from here on, so
        # it cannot run out before the cancels below land (unslowed it
        # finished first on a loaded CPU, and fa.result() returned)
        faults.arm("engine.dispatch", flavor="sleep", times=-1,
                   seconds=0.25)
        fb = eng.submit([5, 6, 8], 4)   # queued behind A
        prefills0 = eng.stats()["prefills"]
        assert eng.cancel(fb.rid)
        with pytest.raises(RequestCancelled):
            fb.result(timeout=60)
        # B never prefilled — cancelled straight out of the queue
        assert eng.stats()["prefills"] == prefills0
        assert eng.cancel(fa.rid)
        with pytest.raises(RequestCancelled):
            fa.result(timeout=60)
        faults.disarm_all()
        # slot freed: a fresh request decodes exactly
        got = eng.submit([5, 6, 8], 4).result(timeout=300)
        assert got["ids"] == _reference(model, params, [5, 6, 8], 4)
        st = eng.stats()
        assert st["cancelled"] == 2 and st["active_slots"] == 0
        # unknown rids are reported dead, not queued for a ghost sweep
        assert not eng.cancel(99999)
    finally:
        eng.close()


def test_backpressure_429_with_retry_after():
    """Queue overflow fast-fails with BackpressureError at the service
    and 429 + Retry-After over HTTP; draining the queue re-admits."""
    from mlcomp_tpu.serve import make_http_server

    model, params = _model_and_params()
    svc = GenerationService(
        model, {"params": params}, batch_sizes=(1,),
        prompt_buckets=(16,), max_new_buckets=(8, 32),
        max_queue_depth=2,
    )
    httpd = make_http_server(svc, "127.0.0.1", 0, "bp-test")
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    port = httpd.server_address[1]
    try:
        svc.submit([5, 6, 7], 4).result(timeout=300)  # warm/compile
        # occupy the ONE slot with a long request, then wedge every
        # dispatch: later submissions stay queued (no free slot), so
        # the overflow state holds still while the contract is probed
        qa: "queue.Queue" = queue.Queue()
        fa = svc.submit([5, 6, 7], 32, stream=qa)
        qa.get(timeout=300)  # decoding now
        faults.arm("engine.dispatch", flavor="sleep", times=-1,
                   seconds=0.5)
        futs = []
        rejected = None
        for _ in range(16):
            try:
                futs.append(svc.submit([5, 6, 7], 8))
            except BackpressureError as e:
                rejected = e
                break
        assert len(futs) == 2, len(futs)  # exactly the queue bound
        assert rejected is not None, "queue bound never enforced"
        assert rejected.reason == "queue_full"
        assert 1.0 <= rejected.retry_after_s <= 60.0
        # the HTTP surface: 429, Retry-After header, machine-readable body
        body = json.dumps({"prompt": [5, 6, 7],
                           "max_new_tokens": 4}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate", data=body,
            headers={"Content-Type": "application/json"},
        )
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=60)
        assert exc.value.code == 429
        retry_after = int(exc.value.headers["Retry-After"])
        assert 1 <= retry_after <= 60
        payload = json.loads(exc.value.read())
        assert payload["reason"] == "queue_full"
        assert svc.stats()["rejected"]["queue_full"] >= 2
        faults.disarm_all()
        fa.result(timeout=300)
        for f in futs:
            f.result(timeout=300)  # queued work still completes
        # drained: admission is open again
        svc.submit([5, 6, 7], 4).result(timeout=300)
    finally:
        faults.disarm_all()
        httpd.shutdown()
        httpd.server_close()
        svc.close()


def test_pipeline_depth_equality_with_cancelled_neighbor():
    """Cancelling one request must not perturb its neighbors' tokens at
    ANY pipeline depth: survivors are bit-identical between depth 1 and
    depth 2, and equal to bare generate."""
    model, params = _model_and_params()
    survivors = {}
    for depth in (1, 2):
        eng = DecodeEngine(model, {"params": params}, slots=2,
                           prompt_buckets=(16,), max_new_cap=24,
                           steps_per_dispatch=1, pipeline_depth=depth)
        try:
            qa: "queue.Queue" = queue.Queue()
            fa = eng.submit([3, 14, 15, 9, 2], 20, stream=qa)
            qb: "queue.Queue" = queue.Queue()
            fb = eng.submit([7, 3, 44], 24, stream=qb)
            qa.get(timeout=300)
            qb.get(timeout=300)  # both decoding
            assert eng.cancel(fb.rid)
            with pytest.raises(RequestCancelled):
                fb.result(timeout=60)
            survivors[depth] = fa.result(timeout=300)["ids"]
        finally:
            eng.close()
    assert survivors[1] == survivors[2]
    assert survivors[1] == _reference(
        model, params, [3, 14, 15, 9, 2], 20
    )


def test_cache_fault_degraded_bypass_returns_exact_tokens():
    """An armed cache.lookup raise is contained to a cache-bypass: the
    request succeeds with the exact cold-prefill tokens, reports 0
    cache_hit_tokens, and increments the degraded counter."""
    model, params = _model_and_params()
    svc = GenerationService(
        model, {"params": params}, batch_sizes=(1, 2),
        prompt_buckets=(16,), max_new_buckets=(8,),
        prefix_cache=True, prefill_chunk=8,
    )
    try:
        shared = [9, 10, 11, 12, 13, 14, 15, 16, 17]
        base = svc.submit(shared + [1], 4).result(timeout=300)
        svc.prefix_cache.flush()
        # sanity: the prefix actually hits when nothing is armed
        hit = svc.submit(shared + [1], 4).result(timeout=300)
        assert hit["cache_hit_tokens"] > 0
        assert hit["ids"] == base["ids"]
        faults.arm("cache.lookup", flavor="raise", times=1)
        deg = svc.submit(shared + [1], 4).result(timeout=300)
        assert deg["ids"] == base["ids"]
        assert deg["cache_hit_tokens"] == 0
        st = svc.engine.stats()
        assert st["cache_degraded"] == 1
        # containment, not poisoning: the next request hits again
        again = svc.submit(shared + [1], 4).result(timeout=300)
        assert again["cache_hit_tokens"] > 0
        assert again["ids"] == base["ids"]
    finally:
        svc.close()


def test_deadline_validation():
    model, params = _model_and_params()
    eng = DecodeEngine(model, {"params": params}, slots=1,
                       prompt_buckets=(16,), max_new_cap=8)
    try:
        with pytest.raises(ValueError, match="deadline_s"):
            eng.submit([1, 2], 4, deadline_s=0)
    finally:
        eng.close()
