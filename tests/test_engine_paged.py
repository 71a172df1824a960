"""Paged device KV (engine ``kv_layout="paged"``, mlcomp_tpu/kvpool).

The acceptance contract: paged outputs are BIT-IDENTICAL to the dense
layout — across cache families (f32 + kv8), pipeline depths,
mid-stream admissions, and the device
prefix-registry COW path — while admission is gated by free pages,
the slot count scales elastically, and nothing leaks a page."""

import functools
import os
import queue
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlcomp_tpu.engine import DecodeEngine
from mlcomp_tpu.kvpool import NoFreePages, RESERVED_PAGES
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

# share the LAYOUT-INDEPENDENT compiled programs across engines: the
# prefill chunk/init/capture programs run on the dense (1, l_buf)
# admission cache whatever the carry layout; the dispatch/insert/fused
# families close over the layout and must NOT cross it
_SHARED_KEYS = ("prefill_init",)
_FNS: dict = {}


def _engine(layout, kv_quant=False, fns_key=None, **kw):
    model, params = _model_and_params(kv_quant)
    kw.setdefault("slots", 2)
    kw.setdefault("prompt_buckets", (16,))
    kw.setdefault("max_new_cap", 12)
    kw.setdefault("steps_per_dispatch", 2)
    kw.setdefault("prefill_chunk", 4)
    if layout == "paged":
        kw["kv_layout"] = "paged"
    eng = DecodeEngine(model, {"params": params}, **kw)
    if fns_key is not None:
        pool = _FNS.setdefault((fns_key, layout, kv_quant), {})
        eng._fns.update(pool)
        eng._fns_pool = pool
    return eng


def _close(eng):
    if hasattr(eng, "_fns_pool"):
        eng._fns_pool.update(eng._fns)
    eng.close()


def _overlapped(layout, kv_quant=False, depth=2):
    """A decodes while B's multi-chunk admission lands mid-stream —
    the same workload shape the fused-admission matrix certifies."""
    model, params = _model_and_params(kv_quant)
    # the dispatch family closes over the paged data path (fused vs
    # lax sandwich) — keep each in its own compiled pool
    attn = os.environ.get("MLCOMP_TPU_PAGED_ATTN", "auto")
    eng = _engine(layout, kv_quant, fns_key=("mtx", attn),
                  pipeline_depth=depth)
    try:
        qa: "queue.Queue" = queue.Queue()
        fa = eng.submit(IDS_A, 10, logprobs=True, stream=qa)
        qa.get(timeout=300)                   # A is decoding
        fb = eng.submit(IDS_B, 6, logprobs=True)
        ra, rb = fa.result(timeout=300), fb.result(timeout=300)
        st = eng.stats()
    finally:
        _close(eng)
    key = lambda r: (r["ids"], r.get("logprobs"))  # noqa: E731
    return {"a": key(ra), "b": key(rb)}, st


@pytest.mark.parametrize("kv_quant", [False, True])
@pytest.mark.parametrize("depth", [1, 2])
def test_paged_bit_identical_to_dense(kv_quant, depth):
    """The default paged data path is FUSED (MLCOMP_TPU_PAGED_ATTN
    auto): attention reads K/V through the page table (paged Pallas
    kernels on the kv8 family, per-layer gathers on f32) and the
    per-token append writes pages in place — no dense view, and still
    bit-identical to the dense engine.  The 10-token decode budget
    also crosses the insert's one-dispatch lookahead, so decode pages
    allocate LAZILY mid-stream (counted, never starved here)."""
    dense, _ = _overlapped("dense", kv_quant, depth=depth)
    paged, st = _overlapped("paged", kv_quant, depth=depth)
    assert paged == dense
    assert st["kv_layout"] == "paged"
    assert st["kv_pool"]["pages_total"] > 0
    assert st["kv_pages_lazy_allocated"] > 0
    assert st["kv_decode_page_failures"] == 0


def test_fused_matches_lax_reference(monkeypatch):
    """MLCOMP_TPU_PAGED_ATTN=lax keeps the PR-7 gather/scatter
    sandwich as the everywhere-reference; the fused default must emit
    the same tokens AND logprobs on the kv8 family (the matrix above
    already pins fused == dense; this pins the reference path too, so
    a bisect between the two envs always means something)."""
    fused, _ = _overlapped("paged", True, depth=2)
    monkeypatch.setenv("MLCOMP_TPU_PAGED_ATTN", "lax")
    # _overlapped keys the shared compiled-program pool on the env, so
    # the reference engine compiles its own sandwich family instead of
    # silently reusing the fused programs
    ref, st = _overlapped("paged", True, depth=2)
    assert ref == fused
    assert st["kv_pages_lazy_allocated"] > 0  # lazy growth is
    # data-path-independent: the sandwich scatters through the same
    # lazily-extended tables


def test_registry_cow_hit_bit_identical():
    """Same-placement shared prefixes: the second request maps the
    first's prompt-prefix pages copy-on-write (registry hit, zero
    host round-trip) and still emits bit-identical tokens; a suffix
    diverging mid-page forks privately (counted)."""
    shared = [9, 10, 11, 12, 13, 14, 15, 16, 17]
    prompts = [shared + [i + 1] for i in range(3)]

    def run(layout):
        eng = _engine(layout, fns_key="cow", prefill_chunk=8)
        try:
            out = [
                eng.submit(p, 6, logprobs=True).result(timeout=300)
                for p in prompts
            ]
            st = eng.stats()
        finally:
            _close(eng)
        return [(r["ids"], r["logprobs"]) for r in out], st

    dense, _ = run("dense")
    paged, st = run("paged")
    assert paged == dense
    kp = st["kv_pool"]
    assert kp["registry_hits"] == 2          # requests 2 and 3
    assert st["kv_registry_hit_tokens"] > 0
    assert kp["shared_mappings"] >= 2
    # the prompts diverge inside the second page -> every hit forks it
    assert kp["cow_forks"] == 2


def test_elastic_scaling_grows_and_shrinks():
    """With a 1-slot floor and page headroom, queued traffic grows the
    live slot count (outputs identical to a wide dense engine), and
    the pool shrinks back to the floor at quiesce."""
    gen = np.random.RandomState(3)
    prompts = [gen.randint(1, 64, size=10).tolist() for _ in range(5)]

    def run(layout, slots, **kw):
        eng = _engine(layout, slots=slots, prefill_chunk=8, **kw)
        try:
            futs = [eng.submit(p, 6, logprobs=True) for p in prompts]
            out = [f.result(timeout=300) for f in futs]
            st = eng.stats()
            if layout == "paged":
                # quiesce: the loop shrinks back to the floor at an
                # idle boundary (give it a few)
                t0 = time.perf_counter()
                while time.perf_counter() - t0 < 10:
                    if len(eng._host) == eng._slots_floor:
                        break
                    time.sleep(0.05)
                assert len(eng._host) == eng._slots_floor
                eng._pool.check_invariants()
        finally:
            _close(eng)
        return [(r["ids"], r["logprobs"]) for r in out], st

    dense, _ = run("dense", slots=4)
    paged, st = run("paged", slots=1, max_slots=4,
                    kv_pages=RESERVED_PAGES + 64)
    assert paged == dense
    assert st["slots_scaled"] >= 2           # grew 1 -> 2 -> 4
    assert st["max_slots"] == 4


def test_admission_defers_then_completes_when_pages_free():
    """Lazy-admission deferral: the gate budgets INITIAL pages
    (prefill + one dispatch of lookahead), so a second request whose
    initial need exceeds what the first leaves free DEFERS at the
    boundary (no fail, FIFO preserved) and completes after the first
    retires — and the first can still grow its lazily-deferred decode
    pages while it is alone.  Zero leaks at quiesce."""
    # B fills its 16-bucket (15 real tokens -> 1 pad slot): its initial
    # need alone exceeds what remains while A (worst case smaller but
    # admitted first) is live in a floor-sized pool
    ids_b15 = [7, 3, 44, 5, 6, 9, 2, 41, 8, 30, 31, 32, 33, 34, 35]
    eng = _engine("paged", slots=2, prefill_chunk=8, max_slots=2)
    one_max = eng._layout.max_pages  # constructor floor: 1 worst case
    need_a = eng._pages_worst({"ids": IDS_A, "n_new": 6})
    need_b0 = eng._pages_initial({"ids": ids_b15, "n_new": 6})
    _close(eng)
    pool_pages = max(need_a, one_max)
    assert need_b0 > pool_pages - need_a  # geometry: B must defer
    eng = _engine("paged", slots=2, prefill_chunk=8, max_slots=2,
                  kv_pages=RESERVED_PAGES + pool_pages)
    try:
        f1 = eng.submit(IDS_A, 6)
        f2 = eng.submit(ids_b15, 6)
        r1 = f1.result(timeout=300)
        r2 = f2.result(timeout=300)
        assert len(r1["ids"]) == 6 and len(r2["ids"]) == 6
        assert eng.stats()["kv_decode_page_failures"] == 0
        pool = eng._pool
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 10:
            pool.reclaim_all()
            if pool.alloc.free_pages == pool.alloc.total_pages:
                break
            time.sleep(0.05)
        assert pool.alloc.free_pages == pool.alloc.total_pages
        pool.check_invariants()
    finally:
        _close(eng)


def test_request_larger_than_pool_fails_typed():
    """The admission gate's defensive bound: a head request whose
    worst-case page need exceeds the WHOLE pool fails typed
    (NoFreePages) instead of deferring forever.  Unreachable through a
    validated constructor today (kv_pages must hold one worst case),
    so the gate is driven directly on a parked loop."""
    from concurrent.futures import Future

    from mlcomp_tpu.engine import _POISON

    eng = _engine("paged", slots=2, prefill_chunk=8)
    try:
        eng._stop.set()
        eng._queue.put(_POISON)
        eng._thread.join(timeout=30)
        fut = Future()
        eng._pending.append({
            "ids": IDS_A, "n_new": 6, "future": fut, "stream": None,
            "rid": 0,
        })
        eng._pages_worst = lambda r: eng._pool.alloc.total_pages + 1
        assert eng._pop_admittable() is None
        assert not eng._pending  # popped, not left to spin
        with pytest.raises(NoFreePages):
            fut.result(timeout=10)
    finally:
        _close(eng)


def test_churn_no_page_leaks():
    """Staggered mixed-length traffic through admissions, finishes,
    and a mid-stream cancel: at quiesce (registry flushed) the pool is
    fully free and every ref-count invariant holds."""
    gen = np.random.RandomState(7)
    eng = _engine("paged", slots=2, max_slots=4, prefill_chunk=8,
                  kv_pages=RESERVED_PAGES + 48)
    try:
        futs = []
        for i in range(10):
            n = int(gen.randint(1, 15))
            futs.append(eng.submit(
                gen.randint(1, 64, size=n).tolist(),
                int(gen.randint(1, 8)),
            ))
        # cancel one mid-flight: the deadline/cancel retirement path
        # must release its pages like a natural finish
        eng.cancel(futs[5].rid)
        done = 0
        for f in futs:
            try:
                f.result(timeout=300)
                done += 1
            except Exception:
                pass
        assert done >= 9
        pool = eng._pool
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 10:
            pool.reclaim_all()
            if pool.alloc.free_pages == pool.alloc.total_pages:
                break
            time.sleep(0.05)
        st = pool.stats()
        assert st["pages_free"] == st["pages_total"], st
        assert st["outstanding_page_leases"] == 0
        pool.check_invariants()
    finally:
        _close(eng)


def test_construction_validation():
    model, params = _model_and_params(False)
    with pytest.raises(ValueError, match="kv_layout"):
        _engine("dense", kv_layout="paged123")
    with pytest.raises(ValueError, match="max_slots"):
        _engine("dense", max_slots=8)
    with pytest.raises(ValueError, match="kv_page_tokens"):
        _engine("dense", kv_pages=64)
    with pytest.raises(ValueError, match="divide"):
        _engine("paged", kv_page_tokens=3)
    with pytest.raises(ValueError, match="below slots"):
        _engine("paged", slots=4, max_slots=2)
    with pytest.raises(ValueError, match="worst-case"):
        _engine("paged", kv_pages=RESERVED_PAGES + 1)


def test_fatblock_recheck_at_scale():
    """The _GEMV_ROWS cliff is re-derived when elastic slots grow (the
    constructor only priced the floor)."""
    from mlcomp_tpu.ops.pallas.quant_matmul import _GEMV_ROWS

    eng = _engine("paged", slots=2, max_slots=256)
    try:
        eng.quant_kernel = True  # the check's only input besides width
        with pytest.warns(UserWarning, match="fat-block"):
            eng._check_scale_fatblock(_GEMV_ROWS + 1)
        # once per engine: the second grow past the cliff stays quiet
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            eng._check_scale_fatblock(_GEMV_ROWS + 2)
    finally:
        eng.quant_kernel = False
        _close(eng)


def test_serve_rejects_no_free_pages_with_page_rate_retry():
    """Admission control on the paged layout: a flood past the page
    budget fast-fails with reason ``no_free_pages`` and a Retry-After
    from the projected page-free rate; accepted requests all finish."""
    model, params = _model_and_params(False)
    svc = GenerationService(
        model, {"params": params}, batch_sizes=(1, 2),
        prompt_buckets=(16,), max_new_buckets=(8,), prefill_chunk=8,
        kv_layout="paged", max_slots=4,
    )
    try:
        gen = np.random.RandomState(1)
        futs, rejects = [], 0
        for _ in range(12):
            try:
                futs.append(svc.submit(
                    gen.randint(1, 64, size=10).tolist(), 8
                ))
            except BackpressureError as e:
                rejects += 1
                assert e.reason == "no_free_pages"
                assert 1.0 <= e.retry_after_s <= 60.0
        assert futs and rejects  # bounded: some in, some 429
        for f in futs:
            assert len(f.result(timeout=300)["ids"]) == 8
        st = svc.stats()
        assert st["rejected"]["no_free_pages"] == rejects
        assert st["kv_pool"]["pages_total"] > 0  # top-level lift
    finally:
        svc.close()
