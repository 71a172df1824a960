"""Async double-buffered dispatch pipeline (engine pipeline_depth):
depth-2 output equality with the synchronous loop across cache
layouts, mixed knobs, mid-stream admission and EOS mid-dispatch;
close/submit races with a dispatch in flight; knob rejection; and the
overlap/latency metrics in stats()."""

import functools
import queue
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlcomp_tpu.engine import _POISON, DecodeEngine, _fail_future
from mlcomp_tpu.models import create_model
from mlcomp_tpu.models.generation import generate
from mlcomp_tpu.serve import GenerationService
from mlcomp_tpu.train.state import init_model


from conftest import (  # the shared compiled-program pool idiom
    close_pooled_engine as _close,
    share_engine_fns as _share,
)


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


def _reference(model, params, ids, n_new, bucket=16, **kw):
    prompt = np.full((1, bucket), 0, np.int32)
    mask = np.zeros((1, bucket), bool)
    prompt[0, bucket - len(ids):] = ids
    mask[0, bucket - len(ids):] = True
    out = generate(
        model, {"params": params}, jnp.asarray(prompt), n_new,
        prompt_mask=jnp.asarray(mask), **kw,
    )
    return np.asarray(out)[0, bucket:].tolist()


def _mixed_workload(model, params, depth, kv_quant):
    """Drive one engine at the given depth through the satellite's
    workload: mixed knobs (greedy + logprobs, repetition penalty, an
    EOS that lands mid-dispatch), mixed lengths across two prompt
    buckets, and a mid-stream admission (C submitted while A streams,
    joining only when a slot frees).  Returns the comparable outputs
    (ids + logprobs; latencies excluded — the pipeline moves time)."""
    rs = np.random.RandomState(11)
    ids_a = rs.randint(1, 64, 5).tolist()
    ids_b = rs.randint(1, 64, 20).tolist()     # lands in the 32 bucket
    ids_c = rs.randint(1, 64, 3).tolist()
    # EOS mid-dispatch: C stops at its first greedy token, i.e. inside
    # step 1 of a K=2 dispatch (deterministic: greedy reference)
    eos_c = _reference(model, params, ids_c, 1, bucket=16)[0]
    eng = _share(
        DecodeEngine(model, {"params": params}, slots=2,
                     prompt_buckets=(16, 32), max_new_cap=12,
                     steps_per_dispatch=2, pipeline_depth=depth),
        ("mixed", kv_quant),
    )
    try:
        qa: "queue.Queue" = queue.Queue()
        fa = eng.submit(ids_a, 9, logprobs=True, stream=qa)
        qa.get(timeout=300)                    # A is decoding
        fb = eng.submit(ids_b, 7, repetition_penalty=1.5)
        fc = eng.submit(ids_c, 6, eos_id=eos_c)  # queues: slots full
        ra = fa.result(timeout=300)
        rb = fb.result(timeout=300)
        rc = fc.result(timeout=300)
        st = eng.stats()
        assert st["pipeline"]["depth"] == depth
        if depth > 1:
            # the pipeline actually ran overlapped at steady state
            assert st["pipeline"]["peak_inflight"] >= 2
    finally:
        _close(eng)
    return {
        "a": (ra["ids"], ra["logprobs"]),
        "b": rb["ids"],
        "c": rc["ids"],
        "eos_c": eos_c,
    }


@pytest.mark.parametrize("kv_quant", [False, True])
def test_depth2_bit_identical_to_depth1(kv_quant):
    """The acceptance equality: a depth-2 pipelined engine's outputs
    (tokens AND logprobs) are bit-identical to depth-1 for a
    mixed-knob, mixed-length workload on both cache layouts, including
    a mid-stream admission and an EOS mid-dispatch — the pipeline may
    reorder host work, never tokens."""
    model, params = _model_and_params(kv_quant)
    d1 = _mixed_workload(model, params, 1, kv_quant)
    d2 = _mixed_workload(model, params, 2, kv_quant)
    assert d1 == d2
    # and both match bare generate (not just each other)
    ids_a = d1["a"][0]
    rs = np.random.RandomState(11)
    ref_a = _reference(model, params, rs.randint(1, 64, 5).tolist(), 9)
    ref_b = _reference(
        model, params, rs.randint(1, 64, 20).tolist(), 7, bucket=32,
        temperature=jnp.zeros((1,)),
        repetition_penalty=jnp.asarray([1.5]),
    )
    assert ids_a == ref_a
    assert d1["b"] == ref_b
    assert d1["c"] == [d1["eos_c"]]            # EOS stopped it at one


def test_pipeline_join_bound_depth2():
    """A join under depth 2 pays at most one fused prefill+decode
    dispatch per run chunk (during which the decode fleet keeps
    advancing — the fused-admission contract), the insert's boundary,
    its own first dispatch and the depth-1 dispatches issued ahead of
    that one's read: between B's ``admit`` and its ``first_token`` on
    the loop's own record, at most 2 + n_chunks + (depth-1) dispatches
    open at K=1 (one chunk here).  Counted on the flight recorder, not
    against ``eng.step_count`` read from this thread: how late a loaded
    machine lets this thread submit B moves no event of that span."""
    model, params = _model_and_params()
    eng = _share(
        DecodeEngine(model, {"params": params}, slots=2,
                     prompt_buckets=(16,), max_new_cap=16,
                     steps_per_dispatch=1, pipeline_depth=2),
        ("k1",),
    )
    try:
        qa: "queue.Queue" = queue.Queue()
        eng.submit([3, 14, 15, 9, 2], 16, stream=qa)
        qa.get(timeout=300)                    # A is decoding
        fb = eng.submit([7, 3, 44], 2)
        assert len(fb.result(timeout=300)["ids"]) == 2
    finally:
        _close(eng)
    events = eng.recorder.export()["traceEvents"]
    life = {e["name"]: e["ts"] for e in events
            if e.get("cat") == "req" and e["id"] == "2" and e["ph"] == "n"}
    opened = [e for e in events
              if e.get("cat") == "disp" and e["name"] == "dispatch"
              and e["ph"] == "b"
              and life["admit"] <= e["ts"] <= life["first_token"]]
    assert 1 <= len(opened) <= 4, (life, opened)


def test_close_with_dispatch_in_flight_fails_pending_exactly_once():
    """The satellite race contract: close() with dispatches in flight
    resolves EVERY pending future exactly once (result or 'closed'
    error, never InvalidStateError), leaves nothing unread in the
    pipeline, and submit-after-close still raises cleanly."""
    model, params = _model_and_params()
    eng = _share(
        DecodeEngine(model, {"params": params}, slots=2,
                     prompt_buckets=(16,), max_new_cap=16,
                     steps_per_dispatch=1, pipeline_depth=2),
        ("k1",),
    )
    q: "queue.Queue" = queue.Queue()
    futs = [eng.submit([3, 14, 15, 9, 2], 16, stream=q)]
    q.get(timeout=300)       # decoding: the pipeline holds a dispatch
    futs += [eng.submit([1, 2], 16) for _ in range(3)]  # active + queued
    if hasattr(eng, "_fns_pool"):
        eng._fns_pool.update(eng._fns)
    eng.close()
    assert not eng._thread.is_alive()
    assert not eng._inflight  # loop finally dropped the unread outputs
    for f in futs:
        assert f.done()
        try:
            f.result(timeout=0)
        except RuntimeError as e:
            assert "closed" in str(e)
    # exactly-once: a second failure attempt on an already-resolved
    # future is a no-op (the _fail_future idempotence contract)
    _fail_future(futs[0], RuntimeError("other"))
    if futs[0].exception() is not None:
        assert "closed" in str(futs[0].exception())
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit([1], 2)


def test_pipeline_depth_validation_and_mesh_default():
    """Depth < 1 is rejected at construction; the default is depth 2
    EVERYWHERE — mesh or not, since the sharded-serving PR (the old
    mesh rejection is gone; tests/test_engine_sharded.py pins the
    sharded equalities)."""
    model, params = _model_and_params()
    kw = dict(slots=2, prompt_buckets=(16,), max_new_cap=8)
    with pytest.raises(ValueError, match="pipeline_depth"):
        DecodeEngine(model, {"params": params}, pipeline_depth=0, **kw)
    eng = DecodeEngine(model, {"params": params}, mesh=object(), **kw)
    try:
        assert eng.pipeline_depth == 2  # mesh default: pipelined too
    finally:
        eng.close()
    eng = DecodeEngine(model, {"params": params}, **kw)
    try:
        assert eng.pipeline_depth == 2  # single-chip default: pipelined
    finally:
        eng.close()


def test_pipeline_overlap_metrics_and_latency_percentiles():
    """stats() carries the overlap metrics (in-flight depth, hidden vs
    wait ms, occupancy) and per-request latency percentiles; the
    service surfaces both (latency at the top level for /healthz and
    the /api/serving proxy)."""
    model, params = _model_and_params()
    svc = GenerationService(
        model, {"params": params}, batch_sizes=(1, 2),
        prompt_buckets=(16,), max_new_buckets=(8,),
    )
    try:
        svc.generate([5, 6, 7], 6)
        svc.generate([9, 2, 4], 6)
        st = svc.stats()
        pl = st["engine"]["pipeline"]
        assert pl["depth"] == 2
        assert pl["issued"] >= 2 and pl["peak_inflight"] == 2
        assert 1.0 <= pl["occupancy"] <= 2.0
        assert pl["host_hidden_ms_per_dispatch"] >= 0.0
        assert pl["resolve_wait_ms_per_dispatch"] >= 0.0
        assert 0.0 <= pl["overlap_efficiency"] <= 1.0
        lat = st["latency"]
        assert lat is st["engine"]["latency"]
        assert lat["samples"] == 2
        for key in ("ttft_ms", "per_token_ms"):
            pcts = lat[key]
            assert pcts["p50"] > 0
            assert pcts["p50"] <= pcts["p95"] <= pcts["p99"]
    finally:
        svc.close()


@pytest.mark.parametrize("depth", [1, 2])
def test_retired_and_unused_rows_are_handed_an_empty_window(
        depth, monkeypatch):
    """A row that holds no request costs the decode attention nothing:
    from the dispatch after a request finishes, its slot's window is
    empty (start >= stop) in every layer and step, as a never-used
    slot's is throughout; the row still decoding beside it returns the
    greedy tokens and logprobs of a run in which that slot never held a
    request; and ``rows_attended_share`` counts the empty rows out."""
    import mlcomp_tpu.ops.pallas.decode_attention as da

    seen = []                        # (start, stop) per kernel call
    real = da.decode_attention

    def spy(q, k8, ks, v8, vs, kv_start=None, kv_stop=None, **kw):
        jax.debug.callback(
            lambda a, b: seen.append((np.asarray(a), np.asarray(b))),
            kv_start, kv_stop, ordered=True,
        )
        return real(q, k8, ks, v8, vs, kv_start=kv_start,
                    kv_stop=kv_stop, **kw)

    monkeypatch.setattr(da, "decode_attention", spy)
    model, params = _model_and_params(kv_quant=True)
    rs = np.random.RandomState(26)
    ids_long, ids_short = rs.randint(1, 64, 6).tolist(), [7, 8, 9]

    def engine():
        return DecodeEngine(
            model, {"params": params}, slots=3, prompt_buckets=(16,),
            max_new_cap=16, steps_per_dispatch=2, pipeline_depth=depth,
        )

    eng = engine()
    try:
        stream: "queue.Queue" = queue.Queue()
        f_long = eng.submit(ids_long, 14, logprobs=True, stream=stream)
        stream.get(timeout=300)              # slot 0 is decoding
        eng.submit(ids_short, 2).result(timeout=300)   # slot 1, retired
        mark = len(seen)
        with_neighbour = f_long.result(timeout=300)
        st = eng.stats()
    finally:
        eng.close()
    after = seen[mark:]
    assert len(after) >= 2 * 2               # >= one dispatch: 2 layers x K
    assert after[0][0][0] < after[0][1][0]   # the live row's window
    assert all(start[1] >= stop[1] for start, stop in after)  # retired
    assert all(start[2] >= stop[2] for start, stop in seen)  # never used
    # the retired row WAS attended while it held its request
    assert any(start[1] < stop[1] for start, stop in seen[:mark])

    att = st["attention"]
    assert att["rows_total"] == 3 * st["pipeline"]["issued"]
    # by the host's mirror: at least the rows the device saw live at a
    # dispatch's first step, and never the unused slot
    first_steps = seen[::2 * 2]
    device_live = sum(int((a < b).sum()) for a, b in first_steps)
    assert device_live <= att["rows_attended"] <= 2 * st["pipeline"]["issued"]
    assert att["rows_attended_share"] == round(
        att["rows_attended"] / att["rows_total"], 4
    )

    eng = engine()
    try:
        alone = eng.submit(ids_long, 14, logprobs=True).result(timeout=300)
    finally:
        eng.close()
    assert with_neighbour["ids"] == alone["ids"]
    assert with_neighbour["logprobs"] == alone["logprobs"]


def test_report_server_serving_proxy_lifts_latency_and_pipeline():
    """/api/serving lifts the daemon's latency percentiles and
    pipeline overlap metrics to the top level of its payload."""
    import json
    import os
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from mlcomp_tpu.report.server import _Handler as ReportHandler

    health = {
        "ok": True,
        "latency": {"samples": 1,
                    "ttft_ms": {"p50": 5.0, "p95": 5.0, "p99": 5.0},
                    "per_token_ms": None},
        "engine": {"pipeline": {"depth": 2, "overlap_efficiency": 0.7}},
    }

    class Stub(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):  # noqa: N802
            if self.path == "/healthz":
                body = json.dumps(health).encode()
                self.send_response(200)
            else:
                body = b'{"error": "disabled"}'
                self.send_response(404)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    stub = ThreadingHTTPServer(("127.0.0.1", 0), Stub)
    threading.Thread(target=stub.serve_forever, daemon=True).start()
    old = os.environ.get("MLCOMP_TPU_SERVE_URL")
    os.environ["MLCOMP_TPU_SERVE_URL"] = (
        f"http://127.0.0.1:{stub.server_address[1]}"
    )
    try:
        out = ReportHandler._r_serving(None, None)
        assert out["reachable"] is True
        assert out["latency"]["ttft_ms"]["p50"] == 5.0
        assert out["pipeline"]["depth"] == 2
        assert out["prefix_cache"] is None  # daemon runs without one
    finally:
        stub.shutdown()
        stub.server_close()
        if old is None:
            os.environ.pop("MLCOMP_TPU_SERVE_URL", None)
        else:
            os.environ["MLCOMP_TPU_SERVE_URL"] = old


# ------------------------------------------------- the loop's span tiling

_EPS_US = 1e-3  # two stamps a nanosecond apart are one stamp
_RUNS = [(1, True), (1, False), (2, True), (2, False)]


@functools.lru_cache(maxsize=None)
def _traced_run(depth, fused):
    """One tiny engine through a lone admission (staged: no fleet to
    ride), a two-chunk admission joining a decoding row (fused chunks
    when ``fused``, staged behind a join drain otherwise), a queued
    third request, and an idle tail; returns the flight recorder's
    events and ``stats()`` taken after the loop thread has exited."""
    model, params = _model_and_params()
    eng = _share(
        DecodeEngine(model, {"params": params}, slots=2,
                     prompt_buckets=(16, 32), max_new_cap=48,
                     steps_per_dispatch=2, pipeline_depth=depth,
                     fused_admission=fused, prefill_chunk=16),
        ("spans",),
    )
    try:
        qa: "queue.Queue" = queue.Queue()
        # A decodes for 24 dispatches: B's two chunks find a fleet to
        # ride however late a loaded machine lets this thread submit B
        futs = [eng.submit([3, 14, 15, 9, 2], 48, stream=qa)]
        qa.get(timeout=300)                    # A is decoding
        futs.append(eng.submit(list(range(1, 21)), 7))  # 32 bucket: 2 chunks
        futs.append(eng.submit([7, 3, 44], 6))          # queues: slots full
        for f in futs:
            f.result(timeout=300)
        # an idle boundary closes (its blocked poll is <= 0.2 s) before
        # the export: the tail the idle_wait assertions look at
        n_idle = sum(e["name"] == "idle_wait" for e in eng.recorder.events)
        for _ in range(3000):
            if sum(e["name"] == "idle_wait"
                   for e in eng.recorder.events) > n_idle:
                break
            time.sleep(0.01)
    finally:
        _close(eng)
    assert not eng._thread.is_alive()
    return eng.recorder.export()["traceEvents"], eng.stats()


def _loop_forest(events):
    """The engine.loop track's complete spans nested by containment:
    (roots, every node); a node is [event, children].  Asserts the
    nesting is STRICT: a span that opens inside another closes inside
    it."""
    tid = next(e["tid"] for e in events
               if e["ph"] == "M" and e["name"] == "thread_name"
               and e["args"]["name"] == "engine.loop")
    spans = sorted(
        (e for e in events if e["ph"] == "X" and e["tid"] == tid),
        key=lambda e: (e["ts"], -e["dur"]),
    )
    roots, nodes, stack = [], [], []
    for e in spans:
        while stack and (stack[-1][0]["ts"] + stack[-1][0]["dur"]
                         <= e["ts"] + _EPS_US):
            stack.pop()
        if stack:
            parent = stack[-1][0]
            assert (e["ts"] + e["dur"]
                    <= parent["ts"] + parent["dur"] + _EPS_US), (e, parent)
        node = [e, []]
        (stack[-1][1] if stack else roots).append(node)
        nodes.append(node)
        stack.append(node)
    return roots, nodes


def _under(node):
    yield node
    for c in node[1]:
        yield from _under(c)


@pytest.mark.parametrize("depth,fused", _RUNS)
def test_loop_spans_nest_and_tile_every_boundary(depth, fused):
    """Every root on the engine.loop track is a ``boundary``; its
    children are the five tiling spans and cover >= 99% of it (they
    share their stamps, so in fact all of it); consecutive boundaries
    leave no gap; a fused chunk's ``prefill_chunk`` sits under an
    ``issue`` under ``admission_tick``, ``insert`` under
    ``admission_complete``."""
    events, _ = _traced_run(depth, fused)
    roots, nodes = _loop_forest(events)
    assert len(roots) > 8
    tiling = {"maintenance", "admission_tick", "issue", "resolve", "unpack"}
    for (b, kids), (nxt, _k) in zip(roots, roots[1:] + [roots[-1]]):
        assert b["name"] == "boundary"
        assert {k[0]["name"] for k in kids} <= tiling
        assert [k[0]["name"] for k in kids[:2]] == [
            "maintenance", "admission_tick"
        ]
        covered = sum(k[0]["dur"] for k in kids)
        assert covered >= 0.99 * b["dur"], (b, covered)
        if nxt is not b:
            assert abs(nxt["ts"] - (b["ts"] + b["dur"])) <= _EPS_US
    parent = {}
    for e, kids in nodes:
        for k in kids:
            parent[id(k[0])] = e["name"]
    for e, _kids in nodes:
        up = parent.get(id(e))
        if e["name"] in ("insert", "join_drain") or (
            e["name"] == "prefill_chunk" and not e["args"]["fused"]
        ):
            assert up in ("admission_tick", "admission_complete",
                          "maintenance"), (e, up)
        if e["name"] == "insert":
            assert up == "admission_complete"
        if e["name"] == "admission_start":
            assert up == "admission_tick" and e["args"]["rid"] in (1, 2, 3)
        if e["name"] == "prefill_chunk" and e["args"]["fused"]:
            assert up == "issue"
        if e["name"] == "unpack":
            assert e["args"]["seq"] >= 1 and e["args"]["tokens"] >= 0
    # each dispatch: one issue, one resolve, one unpack, in that order
    by_seq = {}
    for e, _kids in nodes:
        if e["name"] in ("issue", "resolve", "unpack"):
            by_seq.setdefault(e["args"]["seq"], []).append(
                (e["ts"], e["name"])
            )
    assert by_seq
    for seq, got in by_seq.items():
        assert [n for _, n in sorted(got)] == ["issue", "resolve", "unpack"]
    # the two-chunk admission: fused rode dispatches, staged did not
    done = {e["args"]["rid"]: e["args"] for e, _k in nodes
            if e["name"] == "admission_complete"}
    assert sorted(done) == [1, 2, 3]
    assert [done[r]["chunks"] for r in (1, 2, 3)] == [1, 2, 1]
    assert done[1]["fused_chunks"] == 0  # no fleet to ride
    # how many of B's chunks rode is the scheduler's business (a chunk
    # rides only while a row decodes); that it is counted right is
    # checked against the prefill_chunk spans below
    assert (1 <= done[2]["fused_chunks"] <= 2) if fused else (
        done[2]["fused_chunks"] == 0)
    for rid, args in done.items():
        chunks = [e for e, _k in nodes if e["name"] == "prefill_chunk"
                  and e["args"]["rid"] == rid]
        assert len(chunks) == args["chunks"]
        assert sum(c["args"]["fused"] for c in chunks) == args["fused_chunks"]


@pytest.mark.parametrize("depth,fused", _RUNS)
def test_idle_wait_only_on_idle_boundaries(depth, fused):
    """``idle_wait`` (the blocked queue poll) is the first child of a
    ``maintenance`` span, and opens only with nothing in flight and no
    admitted request unfinished: waiting for traffic is never mixed
    with host work."""
    events, _ = _traced_run(depth, fused)
    _roots, nodes = _loop_forest(events)
    waits = []
    for e, kids in nodes:
        for i, k in enumerate(kids):
            if k[0]["name"] == "idle_wait":
                assert e["name"] == "maintenance" and i == 0
                waits.append(k[0])
    assert waits and len(waits) == sum(
        e["name"] == "idle_wait" for e, _k in nodes
    )
    for w in waits:
        before = [e for e in events if "ts" in e and e["ts"] < w["ts"]]
        dispatch = [e["ph"] for e in before if e.get("cat") == "disp"]
        assert dispatch.count("b") == dispatch.count("e")
        admitted = {e["id"] for e in before if e["name"] == "admit"}
        ended = {e["id"] for e in before
                 if e["name"] == "request" and e["ph"] == "e"}
        assert admitted <= ended, (w, admitted - ended)


@pytest.mark.parametrize("depth,fused", _RUNS)
def test_request_lifecycle_admit_inserted_first_token(depth, fused):
    """Every finished request carries admit < inserted <= first_token
    on its lifecycle track, ``inserted`` repeats the admission's
    chunk counts and says how many loop iterations it held the lane,
    and ``admit`` what its queue wait was booked under."""
    events, _ = _traced_run(depth, fused)
    life = {}
    for e in events:
        if e.get("cat") == "req":
            life.setdefault(e["id"], {})[
                e["name"] if e["ph"] == "n" else e["ph"]
            ] = e
    assert sorted(life) == ["1", "2", "3"]
    for rid, got in life.items():
        assert {"b", "admit", "inserted", "first_token", "e"} <= set(got)
        assert (got["b"]["ts"] <= got["admit"]["ts"]
                < got["inserted"]["ts"] <= got["first_token"]["ts"]
                <= got["e"]["ts"])
    done = {e["args"]["rid"]: e["args"] for e in events
            if e["name"] == "admission_complete"}
    for rid, got in life.items():
        want = done[int(rid)]
        ins = got["inserted"]["args"]
        # a chunk a loop iteration, and ``of`` the bucket's chunks
        assert ins == {
            "chunks": want["chunks"], "fused_chunks": want["fused_chunks"],
            "boundaries": want["chunks"], "of": ins["of"],
        }
        assert ins["of"] >= ins["chunks"]
        assert set(got["admit"]["args"]["blocked_ms"]) == {
            "lane", "slot", "pages"}
    assert life["2"]["inserted"]["args"]["chunks"] == 2


@pytest.mark.parametrize("depth,fused", _RUNS)
def test_host_counter_agrees_with_the_spans(depth, fused):
    """``stats()["pipeline"]``: hidden <= host, and host_ms_per_dispatch
    is what the spans give on the same run (boundary time less resolve
    and idle_wait, over the issue count) - one set of stamps feeds
    both."""
    events, st = _traced_run(depth, fused)
    pl = st["pipeline"]
    assert 0.0 <= pl["host_hidden_ms_per_dispatch"] <= (
        pl["host_ms_per_dispatch"]
    )
    assert 0.0 <= pl["overlap_efficiency"] <= 1.0
    roots, nodes = _loop_forest(events)
    total = sum(b["dur"] for b, _k in roots)
    blocked = sum(e["dur"] for e, _k in nodes
                  if e["name"] in ("resolve", "idle_wait"))
    issues = sum(e["name"] == "issue" for e, _k in nodes)
    assert issues == st["dispatches"] == pl["issued"]
    from_spans = (total - blocked) / 1e3 / issues
    assert pl["host_ms_per_dispatch"] == pytest.approx(
        from_spans, rel=0.01, abs=0.01
    )
    waited = sum(e["dur"] for e, _k in nodes if e["name"] == "resolve")
    assert pl["resolve_wait_ms_per_dispatch"] == pytest.approx(
        waited / 1e3 / issues, rel=0.01, abs=0.01
    )


# ------------------------------- the insert enqueued behind a dispatch

_LONG = [3, 14, 15, 9, 2]
_JOINERS = {
    # 16 bucket, prefill_chunk 16: one chunk an admission
    "one": [[7, 3, 44], [5, 6, 7, 8, 9], [11, 12], [21, 3, 5, 8]],
    # 32 bucket: two chunks an admission
    "several": [list(range(1, 21)), list(range(30, 48)),
                list(range(5, 26)), list(range(40, 59))],
}
_JOINER_NEW = [6, 5, 7, 4]
_BEHIND = [(d, lay, p) for d in (1, 2) for lay in ("dense", "paged")
           for p in ("one", "several")]


def _behind_engine(depth, layout):
    """Three slots, K = 2, parked: the loop thread has exited on a
    fresh engine, and the caller drives it from its own thread."""
    model, params = _model_and_params()
    # max_slots: the paged pool would otherwise grow past three slots
    # behind the queue, and the hand drive runs no elastic tick
    kw = ({"kv_layout": "paged", "max_slots": 3} if layout == "paged"
          else {})
    eng = _share(
        DecodeEngine(model, {"params": params}, slots=3,
                     prompt_buckets=(16, 32), max_new_cap=48,
                     steps_per_dispatch=2, pipeline_depth=depth,
                     prefill_chunk=16, **kw),
        ("behind", layout),
    )
    eng._stop.set()
    eng._queue.put(_POISON)
    eng._thread.join(timeout=60)
    assert not eng._thread.is_alive()
    if eng._watchdog is not None:
        eng._watchdog.join(timeout=60)
    eng._stop.clear()                          # submit() works again
    return eng


def _submit_all(eng, prompt):
    """A long decoder first, then four joiners: with three slots two
    join while it decodes and two wait for a slot, and every joiner's
    chunks find the long row to ride."""
    streams, futs = [], []
    work = [(_LONG, 44)] + list(zip(_JOINERS[prompt], _JOINER_NEW))
    for ids, n_new in work:
        q: "queue.Queue" = queue.Queue()
        futs.append(eng.submit(ids, n_new, logprobs=True, stream=q))
        streams.append(q)
    return streams, futs


def _drain_streams_raw(q):
    got = []
    while True:
        item = q.get(timeout=5)
        if item is None:
            return got
        got.append(item)


def _drain_streams(streams):
    return [[(i["token"], i["logprob"], i["step"])
             for i in _drain_streams_raw(q)] for q in streams]


def _run_loop_until_done(eng, futs):
    """``_loop_body`` on this thread until every future has resolved;
    the recorder's events back."""
    def done(_f):
        if all(f.done() for f in futs):
            eng._exit_loop.set()

    for f in futs:
        f.add_done_callback(done)
    eng._loop_body()
    assert eng._broken is None, eng._broken
    assert all(f.done() for f in futs)
    eng._inflight.clear()        # a last dispatch nobody waits for
    return eng.recorder.export()["traceEvents"]


def _loop_drive(eng, prompt, before=None):
    """Every request queued, then the engine's OWN ``_loop_body`` on
    this thread until the last future resolves: the first boundary
    pumps the whole queue, so the schedule is the loop's alone and the
    same in every run.  Returns per-request stream records, the
    futures, the recorder's events and ``stats()``."""
    streams, futs = _submit_all(eng, prompt)
    if before is not None:
        before(eng)
    events = _run_loop_until_done(eng, futs)
    return _drain_streams(streams), futs, events, eng.stats()


@functools.lru_cache(maxsize=None)
def _behind_run(depth, layout, prompt):
    eng = _behind_engine(depth, layout)
    try:
        records, futs, events, st = _loop_drive(eng, prompt)
        results = [f.result(timeout=0) for f in futs]
    finally:
        _close(eng)
    return records, results, events, st


def _dispatches_before_insert(events):
    """rid -> how many dispatches the loop had issued when it enqueued
    that request's insert."""
    issues = sorted(e["ts"] for e in events
                    if e["ph"] == "X" and e["name"] == "issue")
    return {e["args"]["rid"]: sum(t < e["ts"] for t in issues)
            for e in events if e["ph"] == "X" and e["name"] == "insert"}


def _sync_drive(layout, prompt, schedule):
    """The synchronous drive: staged chunks and ``_run_dispatch`` by
    hand on a parked depth-1 engine, each request admitted after as
    many dispatches as ``schedule`` says."""
    eng = _behind_engine(1, layout)
    try:
        streams, futs = _submit_all(eng, prompt)
        eng._pump_queue()
        n_disp = 0
        while eng._pending:
            req = eng._pending.popleft()
            while n_disp < schedule[req["rid"]]:
                eng._run_dispatch()
                n_disp += 1
            eng._start_admission(req)
            while eng._adm is not None:
                eng._run_admission_chunk()
        while any(s is not None for s in eng._host):
            eng._run_dispatch()
        results = [f.result(timeout=0) for f in futs]
        return _drain_streams(streams), results
    finally:
        _close(eng)


@pytest.mark.parametrize("depth,layout,prompt", _BEHIND)
def test_insert_behind_dispatch_equals_the_synchronous_drive(
        depth, layout, prompt):
    """Tokens, log-probabilities and step numbers of every request,
    with its insert enqueued behind the fused dispatch that carried
    its last chunk, are those of the synchronous hand drive that
    admits each request (staged, on a drained engine) after the same
    number of dispatches."""
    records, results, events, _st = _behind_run(depth, layout, prompt)
    schedule = _dispatches_before_insert(events)
    assert sorted(schedule) == [1, 2, 3, 4, 5]
    assert schedule[1] == 0                    # onto the idle engine
    assert all(schedule[r] > 0 for r in (2, 3, 4, 5))
    want_records, want_results = _sync_drive(layout, prompt, schedule)
    for got, want in zip(records, want_records):
        assert got == want
    for got, want in zip(results, want_results):
        assert got["ids"] == want["ids"]
        assert got["logprobs"] == want["logprobs"]
    # a row's first token comes out of the dispatch after its insert
    for rid, rec in enumerate(records, start=1):
        assert rec[0][2] == 2 * schedule[rid] + 1
        assert [s for _, _, s in rec] == list(
            range(rec[0][2], rec[0][2] + len(rec))
        )


@pytest.mark.parametrize("depth,layout,prompt", _BEHIND)
def test_fused_completion_does_not_drain(depth, layout, prompt):
    """Under an ``admission_complete`` whose chunks rode dispatches
    lies an ``insert`` and no ``join_drain``; at depth 2 the dispatch
    issued next opens with the fused one still unresolved (the
    recorder's ``dispatch`` span carries the in-flight depth at its
    issue)."""
    _records, _results, events, _st = _behind_run(depth, layout, prompt)
    _roots, nodes = _loop_forest(events)
    done = [n for n in nodes if n[0]["name"] == "admission_complete"]
    assert len(done) == 5
    opened = sorted(
        (e["ts"], e["args"]["inflight"]) for e in events
        if e.get("cat") == "disp" and e["name"] == "dispatch"
        and e["ph"] == "b"
    )
    n_chunks = {"one": 1, "several": 2}[prompt]
    for node in done:
        e = node[0]
        below = [n[0]["name"] for n in _under(node)][1:]
        assert "insert" in [k[0]["name"] for k in node[1]]
        if e["args"]["rid"] == 1:
            assert e["args"]["fused_chunks"] == 0   # nothing to ride
            continue
        # the long row decodes throughout: every joiner chunk rode
        assert e["args"]["fused_chunks"] == e["args"]["chunks"] == n_chunks
        assert "join_drain" not in below
        nxt = [d for ts, d in opened if ts >= e["ts"] + e["dur"]]
        if depth == 2 and nxt:
            assert nxt[0] >= 2, (e, nxt[0])


@pytest.mark.parametrize("depth,layout,prompt", _BEHIND)
def test_inserts_behind_dispatch_counter(depth, layout, prompt):
    """``inserts_behind_dispatch`` counts the admissions completed with
    a dispatch unresolved: every joiner, and not the admission onto
    the idle engine.  Occupancy after an issue reaches the depth."""
    _records, _results, _events, st = _behind_run(depth, layout, prompt)
    assert st["prefills"] == 5
    assert st["pipeline"]["inserts_behind_dispatch"] == 4
    assert st["admissions_overlapped"] == 4
    if depth == 2:
        assert st["pipeline"]["occupancy"] > 1.8
    else:
        assert st["pipeline"]["occupancy"] == 1.0


def _fail_second_insert(eng):
    real = eng._insert_fn
    calls = {"n": 0}

    def insert_fn():
        fn = real()

        def call(*a, **kw):
            calls["n"] += 1
            if calls["n"] == 2:
                raise RuntimeError("insert fault")
            return fn(*a, **kw)

        return call

    eng._insert_fn = insert_fn


@pytest.mark.parametrize("layout", ["dense", "paged"])
@pytest.mark.parametrize("fault", ["fused_prefill", "insert"])
def test_behind_dispatch_faults_fail_only_the_joiner(fault, layout):
    """A fault while preparing the joiner's fused chunk, or in the host
    half of its insert (the ``jit_insert`` call, with the fused
    dispatch still in flight), fails that joiner alone: every other
    request's tokens and log-probabilities are the fault-free run's,
    the engine stays healthy and no admission or page is left over."""
    from mlcomp_tpu.utils import faults

    _rec, clean, _ev, _st = _behind_run(2, layout, "one")
    eng = _behind_engine(2, layout)
    try:
        if fault == "fused_prefill":
            # the long row is admitted staged: the first fused prep is
            # the first joiner's
            faults.arm("engine.fused_prefill", flavor="raise", times=1)
            arm, err = None, faults.FaultInjected
        else:
            arm, err = _fail_second_insert, RuntimeError
        _records, futs, _events, st = _loop_drive(eng, "one", before=arm)
        with pytest.raises(err):
            futs[1].result(timeout=0)
        for i in (0, 2, 3, 4):
            got = futs[i].result(timeout=0)
            assert got["ids"] == clean[i]["ids"]
            assert got["logprobs"] == clean[i]["logprobs"]
        # (the loop ran on this thread: _loop_drive saw _broken None)
        assert eng._adm is None and eng._unhealthy_reason is None
        assert st["prefills"] == 4
        assert st["pipeline"]["inserts_behind_dispatch"] == 3
        if layout == "paged":
            # all that is still held is the registry's pin of each
            # INSERTED prompt's page: the failed joiner left none
            pool = st["kv_pool"]
            assert pool["pages_used"] == pool["pages_reclaimable"] == 4
            assert pool["outstanding_page_leases"] == 0
    finally:
        faults.disarm_all()
        _close(eng)


def test_tokens_a_cancelled_row_left_in_flight_are_not_its_successors():
    """A row cancelled with a dispatch in flight still has tokens in
    that dispatch; the request inserted into its slot at the same
    boundary, behind that dispatch, is booked none of them: its tokens
    are bare generate's, and its first step is the dispatch after its
    insert."""
    from mlcomp_tpu.engine import RequestCancelled

    model, params = _model_and_params()
    eng = _behind_engine(2, "dense")

    class CancelAfterTwo(queue.Queue):
        rid = None

        def put(self, item, *a, **kw):   # runs on the loop's thread
            super().put(item, *a, **kw)
            if item is not None and self.qsize() == 2:
                assert eng.cancel(self.rid)

    try:
        doomed = CancelAfterTwo()
        last: "queue.Queue" = queue.Queue()
        futs = [
            eng.submit(_LONG, 44),
            eng.submit([7, 3, 44], 40, stream=doomed),
            eng.submit([5, 6, 7, 8, 9], 40),
            eng.submit([11, 12], 6, logprobs=True, stream=last),
        ]
        doomed.rid = futs[1].rid
        events = _run_loop_until_done(eng, futs)
    finally:
        _close(eng)
    with pytest.raises(RequestCancelled):
        futs[1].result(timeout=0)
    # the successor went in while the cancelled row's last dispatch was
    # unresolved: two in flight when its insert was enqueued
    retire = next(e["ts"] for e in events if e["name"] == "cancel")
    insert = next(e for e in events if e["ph"] == "X"
                  and e["name"] == "insert" and e["args"]["rid"] == 4)
    resolves = sorted(e["ts"] for e in events
                      if e["ph"] == "X" and e["name"] == "resolve")
    issues = sorted(e["ts"] for e in events
                    if e["ph"] == "X" and e["name"] == "issue")
    assert retire < insert["ts"]
    assert not any(retire < t < insert["ts"] for t in resolves)
    assert sum(t < insert["ts"] for t in issues) - sum(
        t < insert["ts"] for t in resolves) == 2
    got = futs[3].result(timeout=0)
    assert got["ids"] == _reference(model, params, [11, 12], 6)
    steps = [item["step"] for item in _drain_streams_raw(last)]
    first = 2 * sum(t < insert["ts"] for t in issues) + 1
    assert steps == list(range(first, first + 6))
