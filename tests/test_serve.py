"""LM serving daemon: the engine-backed service vs direct generate,
bucket padding exactness, concurrent requests, HTTP round trip with
token auth."""

import json
import time
import threading
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlcomp_tpu.models import create_model
from mlcomp_tpu.models.generation import generate
from mlcomp_tpu.engine import bucket
from mlcomp_tpu.serve import GenerationService, load_service
from mlcomp_tpu.train.state import init_model


def _tiny_model():
    return create_model({
        "name": "transformer_lm", "vocab_size": 64, "hidden": 32,
        "layers": 1, "heads": 2, "mlp_dim": 64, "dtype": "float32",
    })


# share the continuous engine's compiled programs across the DEFAULT-
# config services in this module (the _fns idiom from
# tests/test_engine_fused_admit.py): five tests build the identical
# continuous service, and each was paying the full prefill + insert +
# dispatch compile bill — the single biggest line in the tier-1 time
# budget.  Only the exact default config shares; any engine-visible
# kwarg opts out.
_CONT_FNS: dict = {}


def _service(**kw):
    model = _tiny_model()
    prompt = jnp.asarray(np.random.RandomState(0).randint(1, 64, (1, 8)))
    params, mstate = init_model(model, {"x": prompt}, jax.random.PRNGKey(0))
    share = kw == {"batcher": "continuous"}
    kw.setdefault("batch_sizes", (1, 2, 4))
    kw.setdefault("prompt_buckets", (8, 16))
    kw.setdefault("max_new_buckets", (4, 8))
    svc = GenerationService(model, {"params": params, **mstate}, **kw)
    if share:
        eng = svc.engine
        eng._fns.update(_CONT_FNS)
        orig_close = svc.close

        def close(*a, **k):
            _CONT_FNS.update(eng._fns)
            return orig_close(*a, **k)

        svc.close = close
    return model, svc


def test_bucket_helper():
    assert bucket(3, (4, 8), "x") == 4
    assert bucket(4, (4, 8), "x") == 4
    assert bucket(5, (4, 8), "x") == 8
    with pytest.raises(ValueError, match="exceeds"):
        bucket(9, (4, 8), "x")


def test_serve_matches_direct_generate():
    """A bucketed, left-padded request in a pool of idle slots must
    produce exactly what a direct generate on the bare prompt produces
    (greedy, so determinism is total)."""
    model, svc = _service(batcher="continuous")
    try:
        prompt = [3, 14, 15, 9, 2]  # length 5 -> bucket 8, left-padded
        got = svc.generate(prompt, max_new_tokens=4)
        # direct reference: same prompt, no padding at all
        direct = generate(
            model, svc.variables, jnp.asarray([prompt], jnp.int32), 4
        )
        expect = np.asarray(direct)[0, len(prompt):].tolist()
        assert got["ids"] == expect, (got, expect)
    finally:
        svc.close()


def test_serve_batches_concurrent_requests():
    """Concurrent requests decode side by side in the slot pool."""
    model, svc = _service(batcher="continuous")
    try:
        futs = [
            svc.submit([1 + i, 2 + i, 3 + i], max_new_tokens=4)
            for i in range(3)
        ]
        outs = [f.result(timeout=120) for f in futs]
        assert svc.stats()["requests"] == 3
        # each row's output equals its own direct generation
        for i, o in enumerate(outs):
            direct = generate(
                model, svc.variables,
                jnp.asarray([[1 + i, 2 + i, 3 + i]], jnp.int32), 4,
            )
            assert o["ids"] == np.asarray(direct)[0, 3:].tolist()
    finally:
        svc.close()


def test_serve_warmup_really_compiles():
    """warmup() must RUN the hot bucket programs (lazy jit means merely
    constructing the wrappers compiles nothing): the process's compile
    count rises over it, and a request in a warmed bucket adds none."""
    _, svc = _service()

    def compiled():
        return svc.stats()["engine"]["programs"]["compiled"]

    try:
        cold = compiled()
        # a dummy request a prompt bucket, then the ladder's programs
        assert svc.warmup() >= len(svc.prompt_buckets)
        warm = compiled()
        assert warm > cold
        svc.generate([3, 14, 15, 9, 2], 4)
        assert compiled() == warm
    finally:
        svc.close()


def test_serve_request_validation():
    _, svc = _service()
    try:
        with pytest.raises(ValueError, match="non-empty"):
            svc.submit([], 4)
        with pytest.raises(ValueError, match="positive"):
            svc.submit([1], 0)
        with pytest.raises(ValueError, match="exceeds"):
            svc.submit([1] * 99, 4)  # over the largest prompt bucket
        with pytest.raises(ValueError, match="exceeds"):
            svc.submit([1], 99)      # over the largest max_new bucket
    finally:
        svc.close()


def test_serve_eos_trimming():
    """eos_id: generated ids stop at (and include) the first EOS."""
    model = _tiny_model()
    prompt = jnp.asarray(np.random.RandomState(0).randint(1, 64, (1, 8)))
    params, mstate = init_model(model, {"x": prompt}, jax.random.PRNGKey(0))
    # find what the model greedily emits, then declare THAT id the EOS so
    # the trim path provably fires
    first = int(np.asarray(generate(
        model, {"params": params, **mstate}, prompt[:, :4], 4
    ))[0, 4])
    svc = GenerationService(
        model, {"params": params, **mstate},
        batch_sizes=(1,), prompt_buckets=(8,), max_new_buckets=(4,),
        eos_id=first,
    )
    try:
        out = svc.generate(np.asarray(prompt)[0, :4].tolist(), 4)
        assert out["ids"][-1] == first and len(out["ids"]) <= 4
    finally:
        svc.close()


def test_serve_http_round_trip(tmp_path, monkeypatch):
    """cli-level surface: load_service + HTTP server; token auth; healthz."""
    import socket
    from http.server import ThreadingHTTPServer

    from mlcomp_tpu.serve import serve_http

    model_cfg = {
        "name": "transformer_lm", "vocab_size": 64, "hidden": 32,
        "layers": 1, "heads": 2, "mlp_dim": 64, "dtype": "float32",
    }
    svc = load_service(
        model_cfg, ckpt_dir=None,
        batch_sizes=(1, 2), prompt_buckets=(8,), max_new_buckets=(4,),
    )
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    t = threading.Thread(
        target=serve_http, args=(svc,),
        kwargs={"port": port, "model_name": "tiny"}, daemon=True,
    )
    monkeypatch.setenv("MLCOMP_TPU_SERVE_TOKEN", "tok")
    t.start()
    import time as _t

    for _ in range(50):
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/healthz",
                headers={"Authorization": "Bearer tok"},
            )
            with urllib.request.urlopen(req) as r:
                health = json.loads(r.read())
            break
        except OSError:
            _t.sleep(0.1)
    else:
        raise AssertionError("server never came up")
    assert health["ok"] and health["model"] == "tiny"

    # unauthenticated -> 403
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz")
    assert ei.value.code == 403

    body = json.dumps({"prompt": [5, 6, 7], "max_new_tokens": 4}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", data=body,
        headers={"Content-Type": "application/json",
                 "Authorization": "Bearer tok"},
    )
    with urllib.request.urlopen(req) as r:
        out = json.loads(r.read())
    assert len(out["ids"]) == 4
    direct = generate(
        _tiny_model(), svc.variables, jnp.asarray([[5, 6, 7]], jnp.int32), 4
    )
    assert out["ids"] == np.asarray(direct)[0, 3:].tolist()

    # malformed request -> 400
    bad = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", data=b'{"nope": 1}',
        headers={"Authorization": "Bearer tok"},
    )
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(bad)
    assert ei.value.code == 400


def test_serve_sharded_mesh_matches_unsharded():
    """tp-sharded serving (load_service mesh_cfg) must produce the same
    greedy tokens as the single-device service — the SPMD program is a
    layout change, not a math change."""
    from mlcomp_tpu.serve import load_service

    cfg = {"name": "transformer_lm", "vocab_size": 64, "hidden": 32,
           "layers": 1, "heads": 2, "mlp_dim": 64, "dtype": "float32"}
    kw = dict(batch_sizes=(4,), prompt_buckets=(8,), max_new_buckets=(4,))
    plain = load_service(cfg, **kw)
    sharded = load_service(cfg, mesh_cfg={"dp": 4, "tp": 2}, **kw)
    try:
        assert sharded.mesh is not None
        q = sharded.variables["params"]["DecoderLayer_0"]["attn"]["q"][
            "kernel"
        ]
        assert "tp" in q.sharding.spec, q.sharding.spec
        prompt = [3, 14, 15, 9, 2]
        got = sharded.generate(prompt, max_new_tokens=4)
        want = plain.generate(prompt, max_new_tokens=4)
        assert got["ids"] == want["ids"], (got, want)
    finally:
        plain.close()
        sharded.close()


def test_serve_mesh_validates_pallas_layouts_and_batches():
    from mlcomp_tpu.parallel.mesh import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec.from_config({"dp": 2, "tp": 4}))
    model = _tiny_model()
    prompt = jnp.asarray(np.random.RandomState(0).randint(1, 64, (1, 8)))
    params, mstate = init_model(model, {"x": prompt}, jax.random.PRNGKey(0))
    variables = {"params": params, **mstate}
    with pytest.raises(ValueError, match="don't divide"):
        GenerationService(model, variables, mesh=mesh, batch_sizes=(1, 2))
    # heads=2 cannot split over tp=4 for the Pallas kernel islands
    with pytest.raises(ValueError, match="must divide heads"):
        GenerationService(
            model, variables, mesh=mesh, batch_sizes=(2,),
            quantize="kernel",
        )
    kv_model = create_model({
        "name": "transformer_lm", "vocab_size": 64, "hidden": 32,
        "layers": 1, "heads": 2, "mlp_dim": 64, "dtype": "float32",
        "kv_quant": True,
    })
    with pytest.raises(ValueError, match="must divide heads"):
        GenerationService(kv_model, variables, mesh=mesh, batch_sizes=(2,))
    fsdp_mesh = make_mesh(MeshSpec.from_config({"fsdp": 4, "tp": 2}))
    with pytest.raises(ValueError, match="fsdp"):
        GenerationService(
            model, variables, mesh=fsdp_mesh, batch_sizes=(4,),
            quantize="kernel",
        )


def test_serve_sharded_quantized_kernel_matches_single():
    """Round 4: quantize='kernel' + kv_quant compose with a dp×tp mesh —
    the Pallas kernels run inside shard_map islands (quant_matmul with
    Megatron roles, decode_attention with heads over tp) and the greedy
    tokens match the single-device quantized service."""
    from mlcomp_tpu.serve import load_service

    # every tp-sharded dim must stay lane-tileable per device: heads*dh
    # = 256 -> 128/device, mlp 512 -> 256, vocab 256 -> 128
    cfg = {"name": "transformer_lm", "vocab_size": 256, "hidden": 256,
           "layers": 2, "heads": 4, "mlp_dim": 512, "dtype": "float32",
           "kv_quant": True}
    kw = dict(batch_sizes=(4,), prompt_buckets=(8,), max_new_buckets=(4,),
              quantize="kernel")
    plain = load_service(cfg, **kw)
    try:
        want = plain.generate([3, 14, 15, 9, 2], max_new_tokens=4)
    finally:
        plain.close()
    sharded = load_service(cfg, mesh_cfg={"dp": 4, "tp": 2}, **kw)
    try:
        assert sharded.mesh is not None
        got = sharded.generate([3, 14, 15, 9, 2], max_new_tokens=4)
    finally:
        sharded.close()
    assert got["ids"] == want["ids"], (got, want)


def test_rowwise_sampling_matches_static():
    """generation's per-row knob path: greedy rows bit-match the static
    greedy path; neutral knobs (top_k>=V, top_p=1) filter nothing; a
    filtered row only ever emits tokens the filter allows."""
    from mlcomp_tpu.models.generation import (
        process_logits,
        process_logits_rowwise,
        sample_token_rowwise,
    )

    rng = jax.random.PRNGKey(0)
    logits = jax.random.normal(rng, (4, 32)) * 3.0
    # static vs rowwise with identical per-row knobs
    stat = process_logits(logits, 0.7, 5, 0.9)
    row = process_logits_rowwise(
        logits,
        jnp.full((4,), 0.7),
        jnp.full((4,), 5, jnp.int32),
        jnp.full((4,), 0.9),
    )
    np.testing.assert_allclose(
        np.asarray(stat), np.asarray(row), atol=1e-5
    )
    # greedy rows (t=0) match argmax regardless of other rows' knobs
    t = jnp.asarray([0.0, 1.0, 0.0, 2.0])
    toks = sample_token_rowwise(
        rng, logits, t, jnp.full((4,), 32, jnp.int32), jnp.ones((4,))
    )
    am = jnp.argmax(logits, -1)
    assert int(toks[0]) == int(am[0]) and int(toks[2]) == int(am[2])
    # top_k=1 forces argmax even at high temperature
    toks1 = sample_token_rowwise(
        rng, logits, jnp.full((4,), 5.0), jnp.ones((4,), jnp.int32),
        jnp.ones((4,)),
    )
    np.testing.assert_array_equal(np.asarray(toks1), np.asarray(am))


def test_serve_per_request_knobs_share_program():
    """Mixed-knob requests decode through the SAME programs (the knobs
    ride as per-row arrays: a sampled row beside a greedy one builds no
    program the all-greedy warmup did not); the greedy row keeps exact
    determinism."""
    model, svc = _service(batch_sizes=(1, 2))
    try:
        svc.warmup()  # all greedy: every program the engine builds
        programs = set(svc.engine._fns)
        f1 = svc.submit([3, 14, 15, 9, 2], 4)  # greedy
        f2 = svc.submit([7, 3, 44], 4, temperature=5.0, top_k=32)
        r1, r2 = f1.result(timeout=120), f2.result(timeout=120)
        assert len(r2["ids"]) == 4
        assert set(svc.engine._fns) == programs  # none for the mix
        # the greedy row matches a bare greedy generate exactly
        direct = generate(
            model, svc.variables, jnp.asarray([[3, 14, 15, 9, 2]]), 4
        )
        assert r1["ids"] == np.asarray(direct)[0, 5:].tolist()
    finally:
        svc.close()


def test_serve_rejects_bad_knobs():
    _, svc = _service()
    try:
        with pytest.raises(ValueError, match="temperature"):
            svc.generate([1, 2], 4, temperature=-1.0)
        with pytest.raises(ValueError, match="top_k"):
            svc.generate([1, 2], 4, top_k=0)
        with pytest.raises(ValueError, match="top_p"):
            svc.generate([1, 2], 4, top_p=1.5)
    finally:
        svc.close()


def test_serve_per_request_eos():
    """A request-level eos_id stops ITS row only; the neutral row
    beside it runs to its full budget."""
    model, svc = _service(batch_sizes=(1, 2))
    try:
        # find what greedy emits first so we can use it as the eos
        probe = svc.generate([3, 14, 15, 9, 2], 4)
        first = probe["ids"][0]
        f1 = svc.submit([3, 14, 15, 9, 2], 4, eos_id=first)
        f2 = svc.submit([7, 3, 44], 4)
        r1, r2 = f1.result(timeout=120), f2.result(timeout=120)
        assert r1["ids"] == [first]  # stopped at its own eos
        assert len(r2["ids"]) == 4   # unaffected neighbor
    finally:
        svc.close()


def test_serve_logprobs():
    """Requested logprobs align with the emitted ids and equal the
    model's own log-softmax of the greedy logits; requests without the
    flag get no logprobs field."""
    model, svc = _service()
    try:
        prompt = [3, 14, 15, 9, 2]
        r = svc.generate(prompt, 3, logprobs=True)
        assert "logprobs" in r and len(r["logprobs"]) == len(r["ids"])
        assert all(v <= 0.0 for v in r["logprobs"])
        # cross-check the first step against a bare forward
        logits = model.apply(
            svc.variables, jnp.asarray([prompt], jnp.int32)
        )[0, -1]
        expect = float(jax.nn.log_softmax(
            logits.astype(jnp.float32))[r["ids"][0]])
        assert abs(r["logprobs"][0] - expect) < 1e-3
        plain = svc.generate(prompt, 3)
        assert "logprobs" not in plain
    finally:
        svc.close()


def test_serve_repetition_penalty_knob():
    _, svc = _service()
    try:
        r = svc.generate([3, 14, 15, 9, 2], 3, repetition_penalty=1.3)
        assert len(r["ids"]) == 3
        with pytest.raises(ValueError, match="repetition_penalty"):
            svc.generate([1, 2], 3, repetition_penalty=0.0)
    finally:
        svc.close()


def test_serve_moe_sharded_mesh_matches_single():
    """Round 4: moe_lm serves under a dp×ep mesh (experts sharded at
    inference through the decode-shape dense einsum) and produces the
    same greedy tokens as the single-device service."""
    cfg = {"name": "moe_lm", "vocab_size": 64, "hidden": 32, "layers": 2,
           "heads": 2, "n_experts": 4, "moe_every": 2, "dtype": "float32"}
    kw = dict(batch_sizes=(4,), prompt_buckets=(8,), max_new_buckets=(4,))
    plain = load_service(cfg, **kw)
    try:
        want = plain.generate([3, 14, 15, 9, 2], max_new_tokens=4)
    finally:
        plain.close()
    sharded = load_service(cfg, mesh_cfg={"dp": 2, "ep": 4}, **kw)
    try:
        w1 = sharded.variables["params"]["MoELayer_0"]["moe"]["experts_w1"]
        assert "ep" in w1.sharding.spec, w1.sharding.spec
        got = sharded.generate([3, 14, 15, 9, 2], max_new_tokens=4)
    finally:
        sharded.close()
    assert got["ids"] == want["ids"], (got, want)


def test_serve_decode_fused_from_standard_checkpoint(tmp_path):
    """Round 4: `decode_fused: true` in the serve model config restores a
    STANDARD (training-layout) checkpoint and converts the params once —
    greedy tokens equal the unfused service's."""
    from mlcomp_tpu.io.checkpoint import save_checkpoint
    from mlcomp_tpu.serve import load_service

    cfg = {"name": "transformer_lm", "vocab_size": 64, "hidden": 64,
           "layers": 2, "heads": 2, "mlp_dim": 128, "dtype": "float32"}
    model = create_model(cfg)
    prompt = jnp.asarray(np.random.RandomState(4).randint(1, 64, (1, 8)))
    params, mstate = init_model(model, {"x": prompt}, jax.random.PRNGKey(7))
    ckpt = tmp_path / "ckpt"
    save_checkpoint(
        ckpt, {"params": params, "model_state": mstate, "step": 1}, step=1
    )
    kw = dict(batch_sizes=(1,), prompt_buckets=(8,), max_new_buckets=(4,))
    plain = load_service(cfg, ckpt_dir=str(ckpt), **kw)
    try:
        want = plain.generate([3, 14, 15, 9, 2], max_new_tokens=4)
    finally:
        plain.close()
    fused = load_service(
        {**cfg, "decode_fused": True}, ckpt_dir=str(ckpt), **kw
    )
    try:
        fparams = fused.variables["params"]
        assert "qkv" in fparams["DecoderLayer_0"]["attn"]
        got = fused.generate([3, 14, 15, 9, 2], max_new_tokens=4)
    finally:
        fused.close()
    assert got["ids"] == want["ids"], (got, want)
    with pytest.raises(ValueError, match="single-chip"):
        load_service(
            {**cfg, "decode_fused": True}, mesh_cfg={"dp": 8}, **kw
        )


def test_serve_request_count_single_sourced():
    """'requests' is counted in exactly one place: the engine counts,
    warmup dummies excluded, and the top-level stats number is the
    engine's."""
    _, svc = _service(batcher="continuous")
    try:
        svc.warmup()  # dummy submissions must not count
        assert svc.stats()["requests"] == 0
        svc.generate([1, 2, 3], 2)
        st = svc.stats()
        assert st["requests"] == 1
        assert st["engine"]["requests"] == 1
    finally:
        svc.close()


def test_the_window_batcher_is_refused(capsys):
    """One way to serve: the keyword keeps one meaning (two spellings),
    anything else is an unknown value, and the flag is gone."""
    from mlcomp_tpu.cli import main

    model = _tiny_model()
    for gone in ("window", "speculative"):
        with pytest.raises(ValueError, match="expected 'auto'/'continuous'"):
            GenerationService(model, {"params": {}}, batcher=gone)
    for flag in (["--batcher", "window"], ["--batch-window-ms", "10"],
                 ["--engine-pipeline-depth", "1"]):
        with pytest.raises(SystemExit) as ei:
            main(["serve", "--model", "m.yml", *flag])
        assert ei.value.code == 2
        assert "unrecognized arguments" in capsys.readouterr().err


# ------------------------------------------------- device-profile capture


def _ephemeral_server(svc):
    from mlcomp_tpu.serve import make_http_server

    httpd = make_http_server(svc, "127.0.0.1", 0, "tiny")
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, f"http://127.0.0.1:{httpd.server_address[1]}"


def test_profile_bad_dispatches_400():
    _, svc = _service(batcher="continuous")
    httpd, base = _ephemeral_server(svc)
    try:
        for bad in ("0", "-3", "nope", "99999"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"{base}/profile?dispatches={bad}", timeout=30
                )
            assert ei.value.code == 400, bad
            assert "error" in json.loads(ei.value.read())
    finally:
        httpd.shutdown()
        httpd.server_close()
        svc.close()


def test_profile_conflict_409_then_completes():
    """A second capture request while one is armed answers 409; the
    armed capture then completes once decode traffic flows and returns
    the attribution JSON over plain HTTP."""
    _, svc = _service(batcher="continuous")
    httpd, base = _ephemeral_server(svc)
    try:
        result = {}

        def arm():
            try:
                with urllib.request.urlopen(
                    f"{base}/profile?dispatches=1", timeout=120
                ) as r:
                    result["code"] = r.status
                    result["body"] = json.loads(r.read())
            except Exception as e:  # surfaced by the main thread
                result["error"] = repr(e)

        th = threading.Thread(target=arm, daemon=True)
        th.start()
        # wait until the engine really holds the armed capture (the
        # HTTP thread needs a moment to reach the engine)
        for _ in range(200):
            if svc.engine._profile is not None:
                break
            time.sleep(0.01)
        else:
            raise AssertionError("capture never armed")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/profile", timeout=30)
        assert ei.value.code == 409
        body = json.loads(ei.value.read())
        assert body["status"] == "profile_busy"

        # traffic completes the window
        gen = json.dumps({"prompt": [3, 4, 5], "max_new_tokens": 4}).encode()
        req = urllib.request.Request(
            f"{base}/generate", data=gen,
            headers={"Content-Type": "application/json"},
        )
        deadline = time.time() + 120
        while th.is_alive() and time.time() < deadline:
            with urllib.request.urlopen(req, timeout=120) as r:
                json.loads(r.read())
        th.join(timeout=30)
        assert result.get("code") == 200, result
        att = result["body"]
        assert att["dispatches"] >= 1
        assert att["device_time_ms"] > 0
        assert att["host_gap_ms"] >= 0
        assert att["kernels"] and att["families"]
        # a capture happened: stats flips to capture-sourced attribution
        dev = svc.engine.stats()["device"]
        assert dev["source"] == "capture"
        assert dev["captures"] == 1
    finally:
        httpd.shutdown()
        httpd.server_close()
        svc.close()


def test_profile_cancel_disarms_unstarted_capture():
    """The HTTP timeout path: an armed-but-never-started capture (no
    traffic) can be disarmed, failing its future, and a new capture can
    arm afterwards."""
    _, svc = _service(batcher="continuous")
    try:
        fut = svc.profile(dispatches=4)
        assert svc.engine._profile is not None
        assert svc.profile_cancel(fut)
        assert svc.engine._profile is None
        with pytest.raises(RuntimeError, match="cancelled"):
            fut.result(timeout=5)
        fut2 = svc.profile(dispatches=4)  # slot is free again
        assert svc.engine.profile_cancel(fut2)
    finally:
        svc.close()


def test_profile_future_fails_on_close():
    """close() with a capture armed must fail the waiter, not strand
    it."""
    _, svc = _service(batcher="continuous")
    fut = svc.profile(dispatches=2)
    svc.close()
    with pytest.raises(RuntimeError):
        fut.result(timeout=10)
