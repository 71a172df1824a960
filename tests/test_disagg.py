"""Disaggregated prefill/decode: KV pages as the transfer currency.

The acceptance contract: a prompt prefilled on a ``prefill_only``
engine and imported into a paged decode engine emits tokens AND
logprobs bit-identical to the monolithic engine — across cache
families (f32 + kv8) and pipeline depths — while a truncated or
mismatched handoff is rejected TYPED with zero pages, leases, or
slots touched, and the fleet router brokers the two-hop path end to
end over real HTTP."""

import functools
import json
import queue
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlcomp_tpu.engine import DecodeEngine
from mlcomp_tpu.kvpool.transfer import (
    HandoffError,
    decode_handoff,
    encode_handoff,
    rows_to_page_tiles,
)
from mlcomp_tpu.models import create_model
from mlcomp_tpu.serve import BackpressureError, GenerationService
from mlcomp_tpu.train.state import init_model


@functools.lru_cache(maxsize=None)
def _model_and_params(kv_quant=False, seed=0):
    model = create_model({
        "name": "transformer_lm", "vocab_size": 64, "hidden": 64,
        "layers": 2, "heads": 2, "mlp_dim": 128, "dtype": "float32",
        "kv_quant": kv_quant,
    })
    prompt = jnp.asarray(np.random.RandomState(seed).randint(1, 64, (1, 8)))
    params, _ = init_model(model, {"x": prompt}, jax.random.PRNGKey(seed))
    return model, params


IDS_A = [3, 14, 15, 9, 2, 6, 53, 58, 9, 7]
IDS_B = [7, 3, 44, 5, 6]

# share compiled programs across same-geometry engines: prefill-only
# engines compile a subset of the dense family (chunk/init/capture),
# paged engines their own dispatch/insert/import family
_FNS: dict = {}


def _engine(kind, kv_quant=False, **kw):
    model, params = _model_and_params(kv_quant)
    kw.setdefault("prompt_buckets", (16,))
    kw.setdefault("max_new_cap", 12)
    kw.setdefault("steps_per_dispatch", 2)
    kw.setdefault("prefill_chunk", 4)
    if kind == "prefill":
        kw["prefill_only"] = True
        kw.setdefault("slots", 1)
    else:
        kw.setdefault("slots", 2)
        kw["kv_layout"] = "paged"
    eng = DecodeEngine(model, {"params": params}, **kw)
    pool = _FNS.setdefault((kind, kv_quant), {})
    eng._fns.update(pool)
    eng._fns_pool = pool
    return eng


def _close(eng):
    if hasattr(eng, "_fns_pool"):
        eng._fns_pool.update(eng._fns)
    eng.close()


def _result_key(r):
    return (r["ids"], r.get("logprobs"))


# ------------------------------------------------------------ wire format


def test_wire_roundtrip():
    meta = {"s_bucket": 16, "ids": [1, 2, 3], "n_new": 4}
    logits = np.arange(8, dtype=np.float32).reshape(1, 8)
    payloads = [
        np.random.default_rng(0).standard_normal((3, 4, 2, 5)).astype(
            np.float32
        ),
        np.random.default_rng(1).integers(
            -128, 127, (3, 4, 2), dtype=np.int8
        ),
    ]
    blob = encode_handoff(meta, logits, payloads)
    m, lg, pl = decode_handoff(blob)
    assert m["s_bucket"] == 16 and m["ids"] == [1, 2, 3]
    assert m["version"] == 1
    np.testing.assert_array_equal(lg, logits)
    assert len(pl) == 2
    np.testing.assert_array_equal(pl[0], payloads[0])
    np.testing.assert_array_equal(pl[1], payloads[1])
    assert pl[1].dtype == np.int8


def test_wire_bf16_leaves_roundtrip():
    import ml_dtypes

    bf = np.asarray(
        np.random.default_rng(2).standard_normal((2, 4, 3)),
        ml_dtypes.bfloat16,
    )
    blob = encode_handoff({"x": 1}, np.zeros((1, 4), np.float32), [bf])
    _, _, (out,) = decode_handoff(blob)
    assert out.dtype == bf.dtype
    np.testing.assert_array_equal(
        out.view(np.uint16), bf.view(np.uint16)
    )


def test_wire_typed_rejects():
    blob = encode_handoff(
        {"s_bucket": 16}, np.zeros((1, 8), np.float32),
        [np.zeros((2, 4, 2), np.float32)],
    )
    # every truncation point — inside the magic, the header length,
    # the header, each array — rejects typed, as does trailing junk
    for cut in (0, 4, 10, 30, len(blob) - 1):
        with pytest.raises(HandoffError):
            decode_handoff(blob[:cut])
    with pytest.raises(HandoffError):
        decode_handoff(blob + b"x")
    with pytest.raises(HandoffError):
        decode_handoff(b"NOTMAGIC" + blob[8:])
    with pytest.raises(HandoffError):
        decode_handoff(json.dumps({"version": 99}).encode())
    with pytest.raises(HandoffError):
        decode_handoff("not bytes")


def test_rows_to_page_tiles():
    a = np.arange(2 * 8 * 3, dtype=np.float32).reshape(1, 8, 6)[:, :, :3]
    a = np.ascontiguousarray(a)  # (1, 8, 3), slot axis 1
    tiles = rows_to_page_tiles(a, 1, 4)
    assert tiles.shape == (2, 4, 3)
    np.testing.assert_array_equal(tiles[0], a[0, :4])
    np.testing.assert_array_equal(tiles[1], a[0, 4:])
    with pytest.raises(ValueError):
        rows_to_page_tiles(a, 1, 3)  # 8 % 3 != 0


# --------------------------------------------------- engine export/import


def _export_blob(kv_quant, ids, n_new, **req_kw):
    pre = _engine("prefill", kv_quant)
    try:
        res = pre.submit(ids, n_new, **req_kw).result(timeout=300)
        st = pre.stats()
        assert st["handoffs_exported"] == 1, st
        assert st["kv_pages_exported"] == res["pages"] > 0, (st, res)
        assert st["handoff_bytes_exported"] == len(res["handoff"]), st
    finally:
        _close(pre)
    return res["handoff"]


@pytest.mark.parametrize("kv_quant", [False, True])
@pytest.mark.parametrize("depth", [1, 2])
def test_import_bit_identical_to_monolithic(kv_quant, depth):
    """The acceptance bar: decode on imported pages emits tokens AND
    logprobs bit-identical to the monolithic paged engine, for both
    cache families, at pipeline depth 1 and 2."""
    mono = _engine("decode", kv_quant, pipeline_depth=depth)
    try:
        r_mono = mono.submit(IDS_A, 8, logprobs=True).result(timeout=300)
    finally:
        _close(mono)
    blob = _export_blob(kv_quant, IDS_A, 8, logprobs=True)
    dec = _engine("decode", kv_quant, pipeline_depth=depth)
    try:
        r_imp = dec.import_pages(blob).result(timeout=300)
        st = dec.stats()
    finally:
        _close(dec)
    assert _result_key(r_imp) == _result_key(r_mono)
    assert st["handoffs_imported"] == 1
    assert st["kv_pages_imported"] > 0
    assert st["handoff_rejects"] == 0


def test_import_streams_and_interleaves_with_local_traffic():
    """An import admits mid-stream next to a locally-admitted request;
    both finish exact, and the imported request streams its tokens."""
    mono = _engine("decode")
    try:
        r_a = mono.submit(IDS_A, 8).result(timeout=300)
        r_b = mono.submit(IDS_B, 6).result(timeout=300)
    finally:
        _close(mono)
    blob = _export_blob(False, IDS_A, 8)
    dec = _engine("decode")
    try:
        fb = dec.submit(IDS_B, 6)
        toks: "queue.Queue" = queue.Queue()
        fa = dec.import_pages(blob, stream=toks)
        r_imp, r_loc = fa.result(timeout=300), fb.result(timeout=300)
        streamed = []
        while True:
            t = toks.get(timeout=30)
            if t is None:
                break
            streamed.append(t)
    finally:
        _close(dec)
    assert r_imp["ids"] == r_a["ids"]
    assert r_loc["ids"] == r_b["ids"]
    assert [t["token"] for t in streamed] == r_a["ids"]


def test_prefill_only_blob_deterministic_across_cache_hit():
    """The prefill core keeps its prefix cache: a repeated prompt
    prefills from the cache (cache_hit_tokens > 0) and the exported
    blob is BIT-IDENTICAL to the cold one — the cache changes the
    bill, not the pages."""
    from mlcomp_tpu.cache import PrefixKVCache

    model, params = _model_and_params(False)
    cache = PrefixKVCache(max_bytes=1 << 20)
    pre = DecodeEngine(
        model, {"params": params}, slots=1, prompt_buckets=(16,),
        max_new_cap=12, steps_per_dispatch=2, prefill_chunk=4,
        prefill_only=True, prefix_cache=cache,
    )
    try:
        cold = pre.submit(IDS_A, 8).result(timeout=300)
        cache.flush()
        warm = pre.submit(IDS_A, 8).result(timeout=300)
    finally:
        pre.close()
    assert cold["cache_hit_tokens"] == 0
    assert warm["cache_hit_tokens"] > 0
    # logits and every REAL row are bit-identical; only the first
    # page's pad rows (< start_pad, masked out of every attention
    # read) legitimately differ — cold prefill computes don't-care
    # pad K/V there, the cache-hit assembly leaves zeros — plus the
    # per-request header fields (rseed, trace id)
    m_c, lg_c, pl_c = decode_handoff(cold["handoff"])
    m_w, lg_w, pl_w = decode_handoff(warm["handoff"])
    np.testing.assert_array_equal(lg_w, lg_c)
    for a, b in zip(pl_w, pl_c):
        np.testing.assert_array_equal(
            a[1:].view(np.uint8), b[1:].view(np.uint8)
        )
    for k in ("s_bucket", "start_pad", "page_tokens", "n_pages",
              "ids", "leaves"):
        assert m_w[k] == m_c[k], k
    # and the decode-side proof that the pad rows are immaterial:
    # both blobs decode bit-identically
    outs = []
    for blob in (cold["handoff"], warm["handoff"]):
        dec = _engine("decode")
        try:
            outs.append(
                _result_key(dec.import_pages(blob).result(timeout=300))
            )
        finally:
            _close(dec)
    assert outs[0] == outs[1]


def test_import_registers_pages_for_cow_sharing():
    """Imported pages land in the device prefix-page registry exactly
    as if this replica had prefilled them: a later LOCAL admission of
    the same prompt maps them copy-on-write (registry hit) and decodes
    bit-identically."""
    blob = _export_blob(False, IDS_A, 8)
    dec = _engine("decode", kv_pages=48)
    try:
        r_imp = dec.import_pages(blob).result(timeout=300)
        r_loc = dec.submit(IDS_A, 8).result(timeout=300)
        st = dec.stats()
    finally:
        _close(dec)
    assert r_loc["ids"] == r_imp["ids"]
    assert st["kv_registry_hit_tokens"] > 0, st


def test_import_into_near_full_pool_rejects_typed():
    """A service whose pool cannot hold the import's pages fast-fails
    the handoff with the typed ``no_free_pages`` backpressure verdict
    — before anything was allocated (pool stats unchanged)."""
    model, params = _model_and_params(False)
    svc = GenerationService(
        model, {"params": params}, batch_sizes=(1, 2),
        prompt_buckets=(16,), max_new_buckets=(8,),
        prefill_chunk=4, kv_layout="paged", kv_page_tokens=4,
        max_slots=2, kv_pages=9, phase="decode",
    )
    try:
        blob = _export_blob(False, IDS_A, 8)
        # a live stream on a DIFFERENT prompt holds most of the tight
        # pool (same prompt would let the import map the registry's
        # pages COW and sail through)
        other = [5, 8, 21, 33, 41, 17, 29, 60, 11, 13]
        q: "queue.Queue" = queue.Queue()
        fut = svc.submit(other, 8, stream=q)
        q.get(timeout=300)  # decoding: its pages are held
        free_before = svc.engine._pool.stats()["pages_free"]
        with pytest.raises(BackpressureError) as ei:
            svc.import_pages(blob)
        assert ei.value.reason == "no_free_pages"
        assert svc.engine._pool.stats()["pages_free"] == free_before
        fut.result(timeout=300)
    finally:
        svc.close()


def test_truncated_import_zero_leaks_then_recovers():
    """Chaoscheck scenario 10's engine half: a blob truncated at any
    point (the prefill replica died mid-transfer) is rejected TYPED
    with zero pages/leases touched and the reject counted; the intact
    blob then imports fine on the same engine."""
    blob = _export_blob(False, IDS_A, 8)
    dec = _engine("decode")
    try:
        pool = dec._pool
        free0 = pool.stats()["pages_free"]
        for cut in (6, len(blob) // 2, len(blob) - 1):
            with pytest.raises(HandoffError):
                dec.import_pages(blob[:cut])
        # geometry mismatch is typed too: a foreign page quantum
        meta, lg, pl = decode_handoff(blob)
        bad = dict(meta, page_tokens=8)
        bad.pop("arrays", None)
        with pytest.raises(HandoffError):
            dec.import_pages(encode_handoff(bad, lg, pl))
        # ... and so is a prompt past this engine's largest bucket
        # (a hand-rolled topology with diverging prompt_buckets)
        toolong = dict(meta, ids=list(range(1, 25)), s_bucket=32,
                       start_pad=8)
        toolong.pop("arrays", None)
        with pytest.raises(HandoffError):
            dec.import_pages(encode_handoff(toolong, lg, pl))
        st = pool.stats()
        assert st["pages_free"] == free0, st
        assert dec.stats()["handoff_rejects"] == 5
        r = dec.import_pages(blob).result(timeout=300)
        assert len(r["ids"]) == 8
        assert dec.stats()["handoffs_imported"] == 1
    finally:
        _close(dec)


def test_prefill_only_constructor_contract():
    model, params = _model_and_params(False)
    kw = dict(slots=1, prompt_buckets=(16,), max_new_cap=12,
              prefill_chunk=4)
    for bad in (
        {"kv_layout": "paged"},
        {"kv_pages": 8},
        {"max_slots": 2},
    ):
        with pytest.raises(ValueError):
            DecodeEngine(model, {"params": params}, prefill_only=True,
                         **{**kw, **bad})
    # export pages must tile the chunk geometry
    with pytest.raises(ValueError):
        DecodeEngine(model, {"params": params}, prefill_only=True,
                     kv_page_tokens=3, **kw)
    pre = _engine("prefill")
    try:
        with pytest.raises(ValueError):
            pre.submit(IDS_A, 4, stream=queue.Queue())
        assert pre.warm_dispatch_fns() == 0
        assert pre.warm_export_fns() > 0
    finally:
        _close(pre)


def test_import_needs_paged_layout():
    model, params = _model_and_params(False)
    eng = DecodeEngine(
        model, {"params": params}, slots=2, prompt_buckets=(16,),
        max_new_cap=12, steps_per_dispatch=2, prefill_chunk=4,
    )
    try:
        with pytest.raises(ValueError, match="paged"):
            eng.import_pages(b"whatever")
    finally:
        eng.close()


# ------------------------------------------------------------- HTTP layer


@functools.lru_cache(maxsize=None)
def _tiny_model_and_params():
    model = create_model({
        "name": "transformer_lm", "vocab_size": 64, "hidden": 32,
        "layers": 1, "heads": 2, "mlp_dim": 64, "dtype": "float32",
    })
    prompt = jnp.asarray(np.random.RandomState(0).randint(1, 64, (1, 8)))
    params, _ = init_model(model, {"x": prompt}, jax.random.PRNGKey(0))
    return model, params


_TINY_FNS: dict = {}


def _tiny_service(phase, **kw):
    from mlcomp_tpu.serve import make_http_server

    model, params = _tiny_model_and_params()
    if phase in ("decode", "both"):
        kw.setdefault("kv_layout", "paged")
        kw.setdefault("max_slots", 2)
        kw.setdefault("kv_pages", 24)
    svc = GenerationService(
        model, {"params": params}, batch_sizes=(1, 2),
        prompt_buckets=(16,), max_new_buckets=(8,),
        prefill_chunk=8, phase=phase, **kw,
    )
    pool = _TINY_FNS.setdefault(
        (phase if phase == "prefill" else "decode"), {}
    )
    svc.engine._fns.update(pool)
    httpd = make_http_server(svc, "127.0.0.1", 0, "disagg")
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    return svc, httpd, base, pool


def _post(url, body, ctype="application/json", timeout=120):
    data = body if isinstance(body, (bytes, bytearray)) else (
        json.dumps(body).encode()
    )
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": ctype},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


def test_serve_phase_split_http_end_to_end():
    """POST /prefill on a prefill daemon -> handoff blob; POST /import
    on a decode daemon -> tokens bit-identical to the monolithic
    daemon's /generate; a truncated blob -> typed 400 bad_handoff;
    /generate at the prefill daemon -> 409 wrong_phase; /healthz
    surfaces the role on both."""
    prompt = [9, 10, 11, 12, 13, 14, 15, 16, 17, 3]
    mono = _tiny_service("both")
    try:
        code, body, _ = _post(
            mono[2] + "/generate",
            {"prompt": prompt, "max_new_tokens": 4, "logprobs": True},
        )
        assert code == 200, body
        r_mono = json.loads(body)
        _TINY_FNS["decode"].update(mono[0].engine._fns)
    finally:
        mono[1].shutdown()
        mono[1].server_close()
        mono[0].close()

    pre = _tiny_service("prefill", kv_layout="dense")
    dec = _tiny_service("decode")
    try:
        code, hz, _ = _post(pre[2] + "/generate",
                            {"prompt": prompt, "max_new_tokens": 4})
        assert code == 409 and json.loads(hz)["status"] == "wrong_phase"
        with urllib.request.urlopen(pre[2] + "/healthz",
                                    timeout=30) as r:
            assert json.loads(r.read())["phase"] == "prefill"
        with urllib.request.urlopen(dec[2] + "/healthz",
                                    timeout=30) as r:
            assert json.loads(r.read())["phase"] == "decode"

        code, blob, hdrs = _post(
            pre[2] + "/prefill",
            {"prompt": prompt, "max_new_tokens": 4, "logprobs": True},
        )
        assert code == 200, blob
        assert hdrs["Content-Type"] == "application/octet-stream"
        sidecar = json.loads(hdrs["x-mlcomp-handoff"])
        assert sidecar["pages"] > 0
        assert sidecar["prefill_tokens"] == len(prompt)

        code, body, _ = _post(
            dec[2] + "/import", blob, ctype="application/octet-stream",
        )
        assert code == 200, body
        r_imp = json.loads(body)
        assert r_imp["ids"] == r_mono["ids"]
        assert r_imp["logprobs"] == r_mono["logprobs"]

        code, body, _ = _post(
            dec[2] + "/import", blob[: len(blob) - 40],
            ctype="application/octet-stream",
        )
        assert code == 400, body
        assert json.loads(body)["status"] == "bad_handoff"
        assert dec[0].engine.stats()["handoff_rejects"] == 1
    finally:
        for svc, httpd, _base, pool in (pre, dec):
            pool.update(svc.engine._fns)
            httpd.shutdown()
            httpd.server_close()
            svc.close()


def test_router_two_hop_handoff():
    """The fleet path end to end: a router fronting one prefill and
    one decode replica brokers /generate as prefill -> pages ->
    import, with tokens bit-identical to the monolithic daemon,
    handoffs counted, and upstream connections REUSED (keep-alive
    pool)."""
    from types import SimpleNamespace

    from mlcomp_tpu.fleet import (
        CallableLauncher,
        ReplicaManager,
        ReplicaSpec,
        Router,
        make_router_http_server,
    )

    prompt = [9, 10, 11, 12, 13, 14, 15, 16, 17, 5]
    mono = _tiny_service("both")
    try:
        code, body, _ = _post(
            mono[2] + "/generate",
            {"prompt": prompt, "max_new_tokens": 4},
        )
        assert code == 200, body
        r_mono = json.loads(body)
        _TINY_FNS["decode"].update(mono[0].engine._fns)
    finally:
        mono[1].shutdown()
        mono[1].server_close()
        mono[0].close()

    daemons = []

    def launcher_for(phase):
        def spawn(name, port):
            svc, httpd, base, pool = _tiny_service(
                phase, **({"kv_layout": "dense"}
                          if phase == "prefill" else {}),
            )
            daemons.append((svc, httpd, pool))
            return SimpleNamespace(url=base, stop=lambda: None)
        return CallableLauncher(spawn)

    managers = [
        ReplicaManager(
            launcher_for(phase),
            ReplicaSpec(target=1, set_name=phase, phase=phase,
                        health_poll_s=0.2, health_timeout_s=5.0),
        )
        for phase in ("prefill", "decode")
    ]
    router = Router(manager=managers, health_poll_s=0.2,
                    health_timeout_s=5.0)
    rhttpd = None
    try:
        for m in managers:
            m.tick()
        router.poll_once()
        assert router.phase_split_active(), router.status()
        rhttpd = make_router_http_server(router, "127.0.0.1", 0)
        threading.Thread(
            target=rhttpd.serve_forever, daemon=True
        ).start()
        rbase = f"http://127.0.0.1:{rhttpd.server_address[1]}"

        for i in range(3):
            code, body, hdrs = _post(
                rbase + "/generate",
                {"prompt": prompt, "max_new_tokens": 4},
            )
            assert code == 200, body
            assert json.loads(body)["ids"] == r_mono["ids"]
            assert hdrs["x-mlcomp-replica"].startswith("decode")

        st = router.status()
        assert st["phase_split"] is True
        assert st["live_by_phase"] == {
            "both": 0, "prefill": 1, "decode": 1,
        }
        assert st["counts"]["handoffs"] == 3
        assert st["counts"]["handoff_bytes"] > 0
        assert st["counts"]["handoff_failures"] == 0
        # keep-alive reuse: 3 two-hop requests over 2 upstreams dialed
        # at most a couple of sockets, the rest were parked reuses
        assert st["conn_pool"]["reuses"] >= 2, st["conn_pool"]

        # decode-side quiesce: nothing leaked on the import path
        dec_svc = next(
            s for s, _h, _p in daemons if s.phase == "decode"
        )
        eng = dec_svc.engine
        assert eng.stats()["handoffs_imported"] == 3
        # quiesce on the POOL's own state: the response resolves a
        # beat before the loop thread releases the slot's pages
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            pst = eng._pool.stats()
            if pst["pages_used"] == pst["pages_reclaimable"]:
                break
            time.sleep(0.05)
        assert pst["outstanding_page_leases"] == 0, pst
        # every still-used page is registry-held (reclaimable), i.e.
        # no slot or lease leaked a page past quiesce
        assert pst["pages_used"] == pst["pages_reclaimable"], pst
        assert pst["pages_free"] + pst["pages_used"] == (
            pst["pages_total"]
        ), pst
    finally:
        if rhttpd is not None:
            rhttpd.shutdown()
            rhttpd.server_close()
        router.close()
        for m in managers:
            m.close(stop_replicas=True)
        for svc, httpd, pool in daemons:
            pool.update(svc.engine._fns)
            httpd.shutdown()
            httpd.server_close()
            svc.close()
