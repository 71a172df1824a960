"""Adaptive dispatch depth (ISSUE 13): token bit-equality under any K
schedule, the ladder controller's decision table, warmup precompile of
the K ladder, and the double-buffered paged page-fetch's interpret-mode
bit-exactness vs the rolled fetch and the lax (gather + dense kernel)
reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlcomp_tpu.dispatch_control import AdaptiveKController, desired_k
from mlcomp_tpu.engine import DecodeEngine, _POISON
from mlcomp_tpu.models import create_model
from mlcomp_tpu.train.state import init_model

_FNS: dict = {}


def _pooled(eng, *key):
    eng._fns = _FNS.setdefault(key, eng._fns)
    return eng


def _model_and_params(kv_quant=False, seed=0):
    model = create_model({
        "name": "transformer_lm", "vocab_size": 64, "hidden": 64,
        "layers": 2, "heads": 2, "mlp_dim": 128, "dtype": "float32",
        "kv_quant": kv_quant,
    })
    prompt = jnp.asarray(np.random.RandomState(seed).randint(1, 64, (1, 8)))
    params, _ = init_model(model, {"x": prompt}, jax.random.PRNGKey(seed))
    return model, params


# ------------------------------------------------------- bit-equality


@pytest.mark.parametrize("kv_quant", [False, True])
def test_adaptive_vs_pinned_tokens_bit_equal(kv_quant):
    """The tentpole contract: emitted tokens are identical under ANY K
    schedule — pinned 1, pinned 4, and the adaptive controller's own
    schedule — including a mid-stream admission, at f32 and kv8, with
    a sampling row in the mix (the per-step fold_in RNG is the part a
    per-dispatch split would break)."""
    model, params = _model_and_params(kv_quant)
    rs = np.random.RandomState(2)
    prompts = [rs.randint(1, 64, n).tolist() for n in (5, 9, 13, 7)]

    results = {}
    for name, kw in (
        ("k1", {"steps_per_dispatch": 1}),
        ("k4", {"steps_per_dispatch": 4}),
        ("adaptive", {"steps_per_dispatch": "adaptive",
                      "k_ladder": (1, 2, 4)}),
    ):
        eng = _pooled(
            DecodeEngine(model, {"params": params}, slots=2,
                         prompt_buckets=(16,), max_new_cap=12,
                         seed=7, **kw),
            "eq", kv_quant,
        )
        try:
            # 4 prompts through 2 slots: the later two ADMIT mid-stream
            # while the first two decode (fused admission default);
            # one sampled row exercises the RNG stream
            futs = [
                eng.submit(p, 10,
                           temperature=0.8 if i == 1 else 0.0)
                for i, p in enumerate(prompts)
            ]
            results[name] = [f.result(timeout=300)["ids"] for f in futs]
            if name == "adaptive":
                assert eng.adaptive_k
                assert eng.stats()["k_ladder"] == [1, 2, 4]
        finally:
            eng.close()
    assert results["k1"] == results["k4"], "pinned K changed tokens"
    assert results["adaptive"] == results["k1"], (
        "adaptive schedule changed tokens"
    )


def test_k_switch_streams_bit_equal():
    """The stream-visible version of the mid-stream switch: two parked
    engines decode the same two rows, one under a switching schedule,
    one at K=1 — per-row token streams must match exactly."""
    model, params = _model_and_params()
    rs = np.random.RandomState(4)
    prompts = [rs.randint(1, 64, 6).tolist(), rs.randint(1, 64, 11).tolist()]

    def drive(schedule):
        eng = _pooled(
            DecodeEngine(model, {"params": params}, slots=2,
                         prompt_buckets=(16,), max_new_cap=12, seed=5,
                         steps_per_dispatch=schedule[0]),
            "switch2",
        )
        eng._stop.set()
        eng._queue.put(_POISON)
        eng._thread.join(timeout=30)
        from concurrent.futures import Future

        for i, ids in enumerate(prompts):
            req = {
                "ids": ids, "n_new": 8,
                "temperature": 0.6 if i == 1 else 0.0,
                "top_k": 64, "top_p": 1.0, "eos_id": -1,
                "logprobs": False, "repetition_penalty": 1.0,
                "stream": None, "future": Future(), "t_submit": 0.0,
            }
            eng._start_admission(req)
            while eng._adm is not None:
                eng._run_admission_chunk()
        toks = {0: [], 1: []}
        for k in schedule:
            eng.steps_per_dispatch = int(k)
            before = {
                i: (len(sl.emitted) if sl is not None else None)
                for i, sl in enumerate(eng._host)
            }
            snap = {i: sl for i, sl in enumerate(eng._host)}
            eng._run_dispatch()
            for i, sl in snap.items():
                if sl is None or before[i] is None:
                    continue
                toks[i].extend(t for t, _ in sl.emitted[before[i]:])
            if all(s is None for s in eng._host):
                break
        return toks

    assert drive([1, 1, 4, 2, 8, 8]) == drive([1] * 16)


# --------------------------------------------------------- controller


def test_controller_decision_table():
    ladder = (1, 2, 4, 8)
    # (queue_depth, active, slots) -> desired K
    table = [
        ((0, 0, 8), 1),    # idle: TTFT floor
        ((0, 3, 8), 1),    # free slots, nothing queued: stay joinable
        ((0, 8, 8), 8),    # saturated, empty queue: amortize
        ((1, 8, 8), 2),    # one joiner: one rung up
        ((2, 8, 8), 4),
        ((3, 8, 8), 4),
        ((4, 8, 8), 8),    # deep queue: ladder top
        ((64, 2, 8), 8),
    ]
    for (depth, active, slots), want in table:
        assert desired_k(ladder, depth, active, slots) == want, (
            depth, active, slots
        )


def test_controller_hysteresis_dwell_and_quiesce_snap():
    clock = {"t": 0.0}
    ctl = AdaptiveKController((1, 2, 4, 8), hysteresis=3,
                              min_dwell_s=1.0, clock=lambda: clock["t"])
    assert ctl.k == 1
    # deep queue: needs 3 consecutive votes before switching
    assert ctl.decide(8, 8, 8) == 1
    assert ctl.decide(8, 8, 8) == 1
    assert ctl.decide(8, 8, 8) == 8      # third vote switches
    assert ctl.changes == 1
    # a flapping signal inside the dwell window cannot switch back
    clock["t"] += 0.1
    for _ in range(5):
        assert ctl.decide(1, 8, 8) == 8  # votes pile up, dwell blocks
    clock["t"] += 2.0                    # dwell expires
    assert ctl.decide(1, 8, 8) == 2
    assert ctl.changes == 2
    # full quiesce snaps to the floor immediately, no votes needed
    clock["t"] += 0.01                   # inside the new dwell window
    assert ctl.decide(0, 0, 8) == 1
    assert ctl.changes == 3
    # signals matching the current K reset the candidate votes
    assert ctl.decide(8, 8, 8) == 1
    assert ctl.decide(0, 2, 8) == 1      # desired == current: reset
    assert ctl.decide(8, 8, 8) == 1
    assert ctl.decide(8, 8, 8) == 1
    clock["t"] += 2.0
    assert ctl.decide(8, 8, 8) == 8


def test_controller_bad_ladder_rejected():
    with pytest.raises(ValueError):
        AdaptiveKController(())
    with pytest.raises(ValueError):
        AdaptiveKController((0, 2))
    model, params = _model_and_params()
    with pytest.raises(ValueError, match="adaptive"):
        DecodeEngine(model, {"params": params}, slots=2,
                     prompt_buckets=(16,), max_new_cap=8,
                     steps_per_dispatch="sometimes")
    with pytest.raises(ValueError, match="k_ladder"):
        DecodeEngine(model, {"params": params}, slots=2,
                     prompt_buckets=(16,), max_new_cap=8,
                     steps_per_dispatch=4, k_ladder=(1, 4))


# ------------------------------------------------------------- warmup


def test_warmup_precompiles_the_k_ladder():
    """warm_dispatch_fns compiles one plain dispatch per rung (and
    warm_fused_fns one fused program per width per rung), so a
    controller switch mid-serving never compiles on the loop thread."""
    model, params = _model_and_params()
    eng = DecodeEngine(model, {"params": params}, slots=2,
                       prompt_buckets=(16,), max_new_cap=8,
                       steps_per_dispatch="adaptive", k_ladder=(1, 2))
    try:
        eng._stop.set()
        eng._queue.put(_POISON)
        eng._thread.join(timeout=30)
        assert eng.warm_dispatch_fns() == 2
        assert ("dispatch", 1) in eng._fns and ("dispatch", 2) in eng._fns
        assert eng.warm_dispatch_fns() == 0  # idempotent
        n_fused = eng.warm_fused_fns()
        assert n_fused == 2  # one chunk width x two rungs
        assert eng.warm_fused_fns() == 0
        # pinned engines warm exactly their one K
        eng2 = DecodeEngine(model, {"params": params}, slots=2,
                            prompt_buckets=(16,), max_new_cap=8,
                            steps_per_dispatch=4)
        try:
            eng2._stop.set()
            eng2._queue.put(_POISON)
            eng2._thread.join(timeout=30)
            eng2._fns.update(eng._fns)  # shared pool: no recompiles
            assert eng2.k_ladder == (4,)
            assert eng2.warm_dispatch_fns() == 1
        finally:
            eng2.close()
    finally:
        eng.close()


def test_adaptive_metrics_and_stats_surface():
    """The dispatch_k gauge and changes counter exist from the first
    scrape; a live adaptive engine under a burst moves the gauge."""
    model, params = _model_and_params()
    eng = DecodeEngine(model, {"params": params}, slots=2,
                       prompt_buckets=(16,), max_new_cap=8,
                       steps_per_dispatch="adaptive", k_ladder=(1, 2))
    try:
        snap = eng.metrics.snapshot()
        assert "mlcomp_engine_dispatch_k" in snap
        assert "mlcomp_engine_dispatch_k_changes_total" in snap
        rs = np.random.RandomState(5)
        futs = [
            eng.submit(rs.randint(1, 64, 5).tolist(), 6)
            for _ in range(6)
        ]
        for f in futs:
            f.result(timeout=300)
        st = eng.stats()
        assert st["adaptive_k"] is True
        assert st["steps_per_dispatch"] in (1, 2)
        # the 6-deep burst behind 2 slots must have pushed K up at
        # least once (deep queue -> ladder top), i.e. the gauge moved
        assert st["dispatch_k_changes"] >= 1
    finally:
        eng.close()


# -------------------------------------- double-buffered page fetches


def _paged_fixture(rng, B=2, HKV=2, DH=128, T=128, l_buf=512):
    from mlcomp_tpu.kvpool.allocator import NULL_PAGE, RESERVED_PAGES

    MP = l_buf // T
    P = RESERVED_PAGES + B * MP
    kq = rng.integers(-127, 128, (P, HKV, T, DH)).astype(np.int8)
    vq = rng.integers(-127, 128, (P, HKV, T, DH)).astype(np.int8)
    ks = rng.random((P, HKV, 1, T)).astype(np.float32)
    vs = rng.random((P, HKV, 1, T)).astype(np.float32)
    table = np.full((B, MP), NULL_PAGE, np.int32)
    for r in range(B):
        table[r, : MP - r] = RESERVED_PAGES + r * MP + np.arange(MP - r)
    return kq, vq, ks, vs, table


def _gather_dense_np(pages, table, null_page):
    B, MP = table.shape
    out = np.zeros((B, MP) + pages.shape[1:], pages.dtype)
    for b in range(B):
        for p in range(MP):
            if table[b, p] != null_page:
                out[b, p] = pages[table[b, p]]
    return out


def test_double_buffered_fetch_bit_exact():
    """Interpret-mode unit (tentpole 2): the double-buffered page
    fetch is bit-exact vs the rolled fetch AND vs the lax reference
    (page gather feeding the dense kernel), windows clipping blocks on
    both sides and NULL pages in range."""
    from mlcomp_tpu.kvpool.allocator import NULL_PAGE
    from mlcomp_tpu.ops.pallas.decode_attention import (
        decode_attention,
        paged_decode_attention,
    )

    rng = np.random.default_rng(0)
    B, HKV, DH, T, l_buf = 2, 2, 128, 128, 512
    kq, vq, ks, vs, table = _paged_fixture(rng, B, HKV, DH, T, l_buf)
    q = rng.standard_normal((B, 2 * HKV, DH)).astype(np.float32)
    start = np.array([64, 0], np.int32)
    stop = np.array([400, 330], np.int32)
    pages = tuple(jnp.asarray(a) for a in (kq, ks, vq, vs))
    jt = jnp.asarray(table)

    o_roll = paged_decode_attention(
        jnp.asarray(q), *pages, jt, kv_start=jnp.asarray(start),
        kv_stop=jnp.asarray(stop), interpret=True, fetch="rolled",
    )
    o_db = paged_decode_attention(
        jnp.asarray(q), *pages, jt, kv_start=jnp.asarray(start),
        kv_stop=jnp.asarray(stop), interpret=True, fetch="double",
    )
    assert (np.asarray(o_roll) == np.asarray(o_db)).all()

    # lax reference: gather the dense view (zeros where NULL), run the
    # DENSE kernel — bit-equality is the paged family's contract
    k8d = _gather_dense_np(kq, table, NULL_PAGE)
    v8d = _gather_dense_np(vq, table, NULL_PAGE)
    ksd = _gather_dense_np(ks, table, NULL_PAGE)
    vsd = _gather_dense_np(vs, table, NULL_PAGE)
    k8 = k8d.transpose(0, 2, 1, 3, 4).reshape(B, HKV, l_buf, DH)
    v8 = v8d.transpose(0, 2, 1, 3, 4).reshape(B, HKV, l_buf, DH)
    ks2 = ksd.transpose(0, 2, 3, 1, 4).reshape(B, HKV, 1, l_buf)
    vs2 = vsd.transpose(0, 2, 3, 1, 4).reshape(B, HKV, 1, l_buf)
    o_lax = decode_attention(
        jnp.asarray(q), jnp.asarray(k8), jnp.asarray(ks2),
        jnp.asarray(v8), jnp.asarray(vs2), kv_start=jnp.asarray(start),
        kv_stop=jnp.asarray(stop), interpret=True,
    )
    assert (np.asarray(o_lax) == np.asarray(o_db)).all()


def test_wide_chunk_query_tiling_matches_untiled_reference():
    """Tentpole 3: a chunk wider than CHUNK_MAX_SQ runs as query-tiled
    kernel sweeps; each tile's rows must match the per-query
    single-token kernel at the matching causal stop."""
    from mlcomp_tpu.ops.pallas.decode_attention import (
        CHUNK_MAX_SQ,
        decode_attention,
        decode_attention_chunk,
    )

    rng = np.random.default_rng(1)
    B, HKV, DH, T, l_buf = 1, 2, 128, 128, 512
    kq, vq, ks, vs, table = _paged_fixture(rng, B, HKV, DH, T, l_buf)
    S = CHUNK_MAX_SQ + 8   # forces one full tile + one remainder tile
    H = 2 * HKV
    qc = rng.standard_normal((B, S, H, DH)).astype(np.float32)
    start = np.array([16], np.int32)
    stop0 = np.array([300], np.int32)

    from mlcomp_tpu.kvpool.allocator import NULL_PAGE

    k8d = _gather_dense_np(kq, table, NULL_PAGE)
    v8d = _gather_dense_np(vq, table, NULL_PAGE)
    ksd = _gather_dense_np(ks, table, NULL_PAGE)
    vsd = _gather_dense_np(vs, table, NULL_PAGE)
    k8 = k8d.transpose(0, 2, 1, 3, 4).reshape(B, HKV, l_buf, DH)
    v8 = v8d.transpose(0, 2, 1, 3, 4).reshape(B, HKV, l_buf, DH)
    ks2 = ksd.transpose(0, 2, 3, 1, 4).reshape(B, HKV, 1, l_buf)
    vs2 = vsd.transpose(0, 2, 3, 1, 4).reshape(B, HKV, 1, l_buf)

    wide = decode_attention_chunk(
        jnp.asarray(qc), jnp.asarray(k8), jnp.asarray(ks2),
        jnp.asarray(v8), jnp.asarray(vs2), kv_start=jnp.asarray(start),
        kv_stop0=jnp.asarray(stop0), interpret=True,
    )
    wide = np.asarray(wide)
    assert wide.shape == (B, S, H, DH)
    # per-query reference: query j's causal window is [start, stop0+j)
    # — the single-token kernel at kv_stop = stop0 + j computes the
    # same math (allclose, not bitwise: the two kernels' dots run at
    # different sublane widths, so the fp reduction order may differ)
    for j in (0, 5, CHUNK_MAX_SQ - 1, CHUNK_MAX_SQ, S - 1):
        one = decode_attention(
            jnp.asarray(qc[:, j]), jnp.asarray(k8), jnp.asarray(ks2),
            jnp.asarray(v8), jnp.asarray(vs2),
            kv_start=jnp.asarray(start),
            kv_stop=jnp.asarray(stop0 + j), interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(one), wide[:, j], rtol=2e-5, atol=2e-5,
            err_msg=f"query {j}",
        )
    # tile boundaries are exact by construction: the tiled call IS a
    # sequence of plain chunk-kernel calls — slicing the wide output
    # at a tile boundary must equal calling the kernel on that tile
    tile2 = decode_attention_chunk(
        jnp.asarray(qc[:, CHUNK_MAX_SQ:]), jnp.asarray(k8),
        jnp.asarray(ks2), jnp.asarray(v8), jnp.asarray(vs2),
        kv_start=jnp.asarray(start),
        kv_stop0=jnp.asarray(stop0 + CHUNK_MAX_SQ), interpret=True,
    )
    assert (np.asarray(tile2) == wide[:, CHUNK_MAX_SQ:]).all()


def test_paged_fetch_mode_env(monkeypatch):
    import mlcomp_tpu.ops.pallas.decode_attention as da

    assert da.paged_fetch_mode() in ("double", "rolled")
    for mode in ("double", "rolled"):
        monkeypatch.setenv("MLCOMP_TPU_PAGED_FETCH", mode)
        assert da.paged_fetch_mode() == mode
    monkeypatch.setenv("MLCOMP_TPU_PAGED_FETCH", "both")
    with pytest.raises(ValueError, match="MLCOMP_TPU_PAGED_FETCH"):
        da.paged_fetch_mode()
