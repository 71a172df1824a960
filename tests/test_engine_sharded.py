"""Sharded serving: the dispatch pipeline, the paged KV layout, and
the distributed boundary channel under the forced 8-device CPU mesh
(dp×tp) — the sharded-serving PR's acceptance surface.

Bit-equality pairs share compiled programs where the arms differ only
host-side (pipeline depth), and every engine here runs the tiny f32
toy model: the kv8 family's sharded sandwich routes through shard_map
islands this container's jax cannot build (a pre-existing env
limitation covered by the quantized MULTICHIP dryrun legs), while the
f32 family exercises the identical carry/donation/pipeline machinery.
"""

import functools
import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlcomp_tpu.engine import DecodeEngine, NotCoordinator
from mlcomp_tpu.models import create_model
from mlcomp_tpu.models.generation import generate
from mlcomp_tpu.parallel.distributed import BoundaryChannel, ChannelClosed
from mlcomp_tpu.parallel.mesh import MeshSpec, make_mesh, set_current_mesh
from mlcomp_tpu.train.state import init_model

# compiled-program pool (conftest's shared idiom): every engine of
# the same (mesh-ness, layout) config shares one set of jitted
# programs — depth is host-side, so d1/d2 arms compile once
from conftest import (
    close_pooled_engine as _close,
    share_engine_fns as _share,
)


@functools.lru_cache(maxsize=None)
def _model_and_params(seed=0):
    model = create_model({
        "name": "transformer_lm", "vocab_size": 64, "hidden": 64,
        "layers": 2, "heads": 2, "mlp_dim": 128, "dtype": "float32",
    })
    prompt = jnp.asarray(np.random.RandomState(seed).randint(1, 64, (1, 8)))
    params, _ = init_model(model, {"x": prompt}, jax.random.PRNGKey(seed))
    return model, params


@functools.lru_cache(maxsize=None)
def _mesh():
    return make_mesh(MeshSpec.from_config({"dp": 4, "tp": 2}))


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


def _mixed_workload(model, params, depth, kv_layout, eos_c):
    """Mid-stream admission + EOS-mid-dispatch workload on a sharded
    engine: A streams while B joins (slots full → C queues and joins
    mid-stream), C stops at an EOS landing inside a K=2 dispatch."""
    mesh = _mesh()
    set_current_mesh(mesh)
    rs = np.random.RandomState(11)
    ids_a = rs.randint(1, 64, 5).tolist()
    ids_b = rs.randint(1, 64, 7).tolist()
    ids_c = rs.randint(1, 64, 3).tolist()
    eng = _share(
        DecodeEngine(model, {"params": params}, slots=2,
                     prompt_buckets=(16,), max_new_cap=10,
                     steps_per_dispatch=2, pipeline_depth=depth,
                     kv_layout=kv_layout, mesh=mesh),
        ("sharded", kv_layout),
    )
    try:
        qa: "queue.Queue" = queue.Queue()
        fa = eng.submit(ids_a, 9, logprobs=True, stream=qa)
        qa.get(timeout=300)                    # A is decoding
        fb = eng.submit(ids_b, 7)
        fc = eng.submit(ids_c, 6, eos_id=eos_c)  # queues: slots full
        ra = fa.result(timeout=300)
        rb = fb.result(timeout=300)
        rc = fc.result(timeout=300)
        st = eng.stats()
        assert st["pipeline"]["depth"] == depth
        if depth > 1:
            assert st["pipeline"]["peak_inflight"] >= 2
    finally:
        _close(eng)
    return {"a": (ra["ids"], ra["logprobs"]), "b": rb["ids"],
            "c": rc["ids"]}


def test_sharded_depth2_bit_identical_to_depth1_and_paged_to_dense():
    """The acceptance equalities, in one compiled workload: under the
    8-device dp×tp mesh a depth-2 pipelined engine emits tokens (and
    logprobs) bit-identical to depth-1, the sharded PAGED layout
    matches sharded dense bit-exact, and all of them match bare
    generate — with a mid-stream admission and an EOS mid-dispatch in
    the mix."""
    model, params = _model_and_params()
    rs = np.random.RandomState(11)
    ids_a = rs.randint(1, 64, 5).tolist()
    rs.randint(1, 64, 7)
    ids_c = rs.randint(1, 64, 3).tolist()
    eos_c = _reference(model, params, ids_c, 1)[0]
    d1 = _mixed_workload(model, params, 1, "dense", eos_c)
    d2 = _mixed_workload(model, params, 2, "dense", eos_c)
    p2 = _mixed_workload(model, params, 2, "paged", eos_c)
    assert d1 == d2, (d1, d2)
    assert p2 == d2, (p2, d2)
    assert d1["a"][0] == _reference(model, params, ids_a, 9)
    assert d1["c"] == [eos_c]                  # EOS stopped it at one


def test_mesh_defaults_pipelined_and_remaining_rejections_name_followup():
    """Engine(..., mesh=...) no longer rejects pipeline_depth=2 or
    kv_layout='paged'; the default depth under a mesh is 2; the
    REMAINING incompatibilities (prefix cache, forced pallas
    knobs) are rejected with messages naming the follow-up."""
    model, params = _model_and_params()
    kw = dict(slots=2, prompt_buckets=(16,), max_new_cap=8)

    class FakeMesh:  # construction-time checks precede any mesh use
        pass

    eng = DecodeEngine(model, {"params": params}, mesh=FakeMesh(), **kw)
    try:
        assert eng.pipeline_depth == 2  # mesh default: pipelined too
    finally:
        eng.close()
    eng = DecodeEngine(model, {"params": params}, mesh=FakeMesh(),
                       pipeline_depth=2, **kw)
    try:
        assert eng.pipeline_depth == 2  # explicit depth accepted
    finally:
        eng.close()
    with pytest.raises(ValueError, match="follow-up"):
        import os

        os.environ["MLCOMP_TPU_PAGED_ATTN"] = "pallas"
        try:
            DecodeEngine(model, {"params": params}, mesh=FakeMesh(),
                         kv_layout="paged", **kw)
        finally:
            os.environ.pop("MLCOMP_TPU_PAGED_ATTN", None)
    with pytest.raises(ValueError, match="follow-up"):
        import os

        os.environ["MLCOMP_TPU_PAGE_GATHER"] = "pallas"
        try:
            DecodeEngine(model, {"params": params}, mesh=FakeMesh(),
                         kv_layout="paged", **kw)
        finally:
            os.environ.pop("MLCOMP_TPU_PAGE_GATHER", None)


def test_donation_sharding_round_trip():
    """The donated sharded carry keeps its shardings through the
    dispatch chain: page arrays are BORN tp-sharded at the kv-head
    axis (tables replicated) and hold exactly that sharding after
    admissions, dispatches, retirements, and lazy page growth — the
    runtime half of graftcheck's donation-sharding rule."""
    from jax.sharding import PartitionSpec as P

    model, params = _model_and_params()
    mesh = _mesh()
    set_current_mesh(mesh)
    eng = _share(
        DecodeEngine(model, {"params": params}, slots=2,
                     prompt_buckets=(16,), max_new_cap=10,
                     steps_per_dispatch=2, pipeline_depth=2,
                     kv_layout="paged", mesh=mesh),
        ("sharded", "paged"),
    )
    try:
        from jax.sharding import NamedSharding

        mesh_ = eng.mesh
        pages = eng._dstate["pages"]
        born = [p.sharding for p in pages]
        # cached_key pages: (P, T, Hkv, dh) — heads at axis 2, tp=2
        # divides Hkv=2, so the spec pins tp there
        assert born[0].is_equivalent_to(
            NamedSharding(mesh_, P(None, None, "tp")), pages[0].ndim
        ), born[0].spec
        assert eng._dstate["table"].sharding.is_equivalent_to(
            NamedSharding(mesh_, P()), 2
        )
        eng.submit([3, 14, 15, 9, 2], 8).result(timeout=300)
        eng.submit([7, 3, 44], 8).result(timeout=300)
        after = [p.sharding for p in eng._dstate["pages"]]
        assert all(
            a.is_equivalent_to(b, p.ndim)
            for a, b, p in zip(after, born, eng._dstate["pages"])
        ), [(a.spec, b.spec) for a, b in zip(after, born)]
        assert eng._dstate["table"].sharding.is_equivalent_to(
            NamedSharding(mesh_, P()), 2
        )
    finally:
        _close(eng)


# ---------------------------------------------------- boundary channel


def test_boundary_channel_framing_and_close():
    """The TCP broadcast channel in isolation: records arrive in
    order, close() unblocks a waiting recv with ChannelClosed, and a
    single-process channel is inert."""
    from mlcomp_tpu.scheduler.worker import _free_port

    inert = BoundaryChannel(num_processes=1, process_id=0)
    assert inert.is_coordinator
    inert.send({"k": 1})   # no-op, no sockets
    inert.close()

    port = _free_port()
    follower_box: dict = {}

    def follow():
        ch = BoundaryChannel(num_processes=2, process_id=1,
                             address="127.0.0.1:0", port=port)
        follower_box["ch"] = ch
        follower_box["recs"] = [ch.recv(), ch.recv()]
        try:
            ch.recv()
        except ChannelClosed:
            follower_box["closed"] = True

    t = threading.Thread(target=follow, daemon=True)
    t.start()
    coord = BoundaryChannel(num_processes=2, process_id=0, port=port)
    coord.send({"new": [], "k": 2})
    coord.send({"new": [{"rid": 7}], "retired": [[7, "cancelled"]]})
    time.sleep(0.2)
    coord.close()
    t.join(timeout=10)
    assert not t.is_alive()
    assert follower_box["recs"][0]["k"] == 2
    assert follower_box["recs"][1]["retired"] == [[7, "cancelled"]]
    assert follower_box.get("closed") is True
    follower_box["ch"].close()


def test_single_process_gang_follower_replays_coordinator():
    """A REAL coordinator/follower pair over localhost TCP in one
    process (no jax.distributed needed): the follower engine replays
    the coordinator's broadcast boundaries — same admissions, same
    dispatch count, same emitted tokens, cancel retirements included —
    and its submit surface is closed (NotCoordinator).  The stop
    record ends the follower's loop when the coordinator closes."""
    from mlcomp_tpu.scheduler.worker import _free_port

    model, params = _model_and_params()
    mesh = _mesh()
    set_current_mesh(mesh)
    port = _free_port()
    box: dict = {}

    def connect_follower():
        box["chf"] = BoundaryChannel(num_processes=2, process_id=1,
                                     address="127.0.0.1:0", port=port)

    t = threading.Thread(target=connect_follower, daemon=True)
    t.start()
    chc = BoundaryChannel(num_processes=2, process_id=0, port=port)
    t.join(timeout=10)
    chf = box["chf"]
    kw = dict(slots=2, prompt_buckets=(16,), max_new_cap=10,
              steps_per_dispatch=2, pipeline_depth=2, mesh=mesh)
    eng_c = _share(
        DecodeEngine(model, {"params": params}, dist=chc, **kw),
        ("gang",),
    )
    eng_f = _share(
        DecodeEngine(model, {"params": params}, dist=chf, **kw),
        ("gang",),
    )
    try:
        assert eng_c.is_coordinator and not eng_f.is_coordinator
        with pytest.raises(NotCoordinator):
            eng_f.submit([1, 2, 3], 4)
        r1 = eng_c.submit([3, 14, 15, 9, 2], 6).result(timeout=300)
        assert r1["ids"] == _reference(model, params,
                                       [3, 14, 15, 9, 2], 6)
        # a cancel retirement rides the broadcast too
        qs: "queue.Queue" = queue.Queue()
        f2 = eng_c.submit([7, 3, 44], 10, stream=qs)
        qs.get(timeout=300)                   # decoding
        assert eng_c.cancel(f2.rid)
        with pytest.raises(Exception):
            f2.result(timeout=300)
        deadline = time.time() + 120
        while time.time() < deadline:
            stf = eng_f.stats()
            if (stf["emitted_tokens"] == eng_c.stats()["emitted_tokens"]
                    and stf["cancelled"] == 1):
                break
            time.sleep(0.05)
        stf = eng_f.stats()
        stc = eng_c.stats()
        assert stf["emitted_tokens"] == stc["emitted_tokens"]
        assert stf["prefills"] == stc["prefills"] == 2
        assert stf["cancelled"] == 1
        assert stc["mesh"]["coordinator"] is True
        assert stf["mesh"]["coordinator"] is False
    finally:
        # coordinator first: its loop's finally broadcasts the stop
        # record that ends the follower's loop
        _close(eng_c)
        eng_f._thread.join(timeout=60)
        alive = eng_f._thread.is_alive()
        _close(eng_f)
        assert not alive  # the stop record ended the follower loop


@pytest.mark.slow
def test_two_process_distributed_serve_gang(tmp_path):
    """The real multi-host path: 2 jax.distributed processes × 4
    virtual CPU devices serve one SPMD gang — process 0 fronts, the
    follower replays, tokens match a single-host reference.  Slow:
    spawns fresh JAX processes; skipped (not failed) where the CPU
    backend cannot run multi-process computations (this container's
    jax — the driver environment can)."""
    import json
    import os
    import subprocess
    import sys

    from mlcomp_tpu.scheduler.worker import _free_port
    from mlcomp_tpu.serve import load_service

    cfg = {"name": "transformer_lm", "vocab_size": 64, "hidden": 64,
           "layers": 2, "heads": 4, "mlp_dim": 128, "dtype": "float32"}
    ref = load_service(cfg, batch_sizes=(2,), prompt_buckets=(16,),
                       max_new_buckets=(8,), metrics_history_interval=0)
    try:
        e1 = ref.generate([3, 14, 15, 9, 2], 6)["ids"]
        e2 = ref.generate([7, 3, 44], 6)["ids"]
    finally:
        ref.close()

    child = tmp_path / "gang_child.py"
    child.write_text(
        "import json, os\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from mlcomp_tpu.parallel.distributed import ("
        "BoundaryChannel, init_distributed)\n"
        "init_distributed()\n"
        "ch = BoundaryChannel()\n"
        "from mlcomp_tpu.serve import load_service\n"
        f"cfg = {cfg!r}\n"
        "svc = load_service(cfg, mesh_cfg={'dp': 2, 'tp': 4},\n"
        "    batch_sizes=(2,), prompt_buckets=(16,),\n"
        "    max_new_buckets=(8,), metrics_history_interval=0,\n"
        "    dist=ch)\n"
        "pid = int(os.environ['MLCOMP_TPU_PROCESS_ID'])\n"
        "try:\n"
        "    svc.warmup()\n"
        "    if pid == 0:\n"
        "        r1 = svc.submit([3, 14, 15, 9, 2], 6).result(300)\n"
        "        r2 = svc.submit([7, 3, 44], 6).result(300)\n"
        "        want = json.loads(os.environ['GANG_EXPECTED'])\n"
        "        assert [r1['ids'], r2['ids']] == want, (r1, r2, want)\n"
        "        assert svc.stats()['ready'] is True\n"
        "    else:\n"
        "        assert svc.stats()['ready'] is False\n"
        "        svc.engine._thread.join(timeout=300)\n"
        "        assert svc.engine.stats()['dispatches'] >= 3\n"
        "finally:\n"
        "    svc.close()\n"
        "print('gang proc', pid, 'ok', flush=True)\n"
    )
    port, sync_port = _free_port(), _free_port()
    env_base = {
        k: v for k, v in os.environ.items()
        if "MLCOMP" not in k and k not in ("XLA_FLAGS",)
    }
    env_base["JAX_PLATFORMS"] = "cpu"
    env_base["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env_base["MLCOMP_TPU_COORDINATOR"] = f"127.0.0.1:{port}"
    env_base["MLCOMP_TPU_NUM_PROCESSES"] = "2"
    env_base["MLCOMP_TPU_SYNC_PORT"] = str(sync_port)
    env_base["GANG_EXPECTED"] = json.dumps([e1, e2])
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env_base["PYTHONPATH"] = os.pathsep.join(
        p for p in (repo, os.environ.get("PYTHONPATH")) if p
    )
    procs = []
    for pid in range(2):
        env = dict(env_base, MLCOMP_TPU_PROCESS_ID=str(pid))
        procs.append(subprocess.Popen(
            [sys.executable, str(child)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        ))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=600)
            outs.append(out.decode(errors="replace"))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    blob = "\n".join(outs)
    if "Multiprocess computations aren't implemented" in blob:
        pytest.skip("CPU backend cannot run multi-process computations "
                    "in this jax build (pre-existing env limitation)")
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, (
            f"gang process {pid} exited {p.returncode}:\n{out[-3000:]}"
        )
