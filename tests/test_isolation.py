"""Per-task subprocess isolation: crash containment, pinning, stop-kill.

These tests spawn real child processes (scheduler/child.py), so each task
pays a fresh-interpreter JAX import (~seconds on CPU) — kept to a handful
of tasks for suite-time sanity.
"""

import json
import os
import time

import pytest

from mlcomp_tpu.dag.schema import DagSpec, TaskSpec, ResourceSpec, TaskStatus
from mlcomp_tpu.db.store import Store
from mlcomp_tpu.scheduler.worker import Worker


def _submit(store, *tasks):
    dag = DagSpec(name="iso", project="t", tasks=tuple(tasks))
    dag_id = store.submit_dag(dag)
    names = [t.name for t in tasks]
    store.set_task_status(dag_id, names, TaskStatus.QUEUED)
    return dag_id


def _row(store, dag_id, name):
    return {r["name"]: r for r in store.task_rows(dag_id)}[name]


@pytest.fixture()
def store(tmp_db):
    s = Store(tmp_db)
    yield s
    s.close()


def test_child_process_isolation_and_result_roundtrip(store, tmp_path):
    """The task really runs in another process and its result comes back."""
    dag_id = _submit(
        store,
        TaskSpec(
            name="pid",
            executor="shell",
            args={"command": "echo pid $$"},
        ),
    )
    w = Worker(store, name="iso-w", chips=0, workdir=str(tmp_path),
               isolate=True, load_jax_executors=False)
    assert w.run_once() is True
    row = _row(store, dag_id, "pid")
    assert row["status"] == TaskStatus.SUCCESS.value
    assert json.loads(row["result"]) == {"returncode": 0}
    logs = " ".join(l["message"] for l in store.task_logs(row["id"]))
    assert "spawned child pid" in logs


def test_hard_child_death_survives_and_worker_claims_next(store, tmp_path):
    """VERDICT r1 'done' criterion: a kill-flavor fault inside an executor
    no longer kills the worker loop; the worker claims the next task."""
    dag_id = _submit(
        store,
        TaskSpec(name="victim", executor="noop", args={}),
        TaskSpec(name="next", executor="noop", args={}),
    )
    w = Worker(
        store, name="iso-w", chips=0, workdir=str(tmp_path), isolate=True,
        load_jax_executors=False,
        # armed in the CHILD's env only: os._exit(137) mid-run_task
        child_env={"MLCOMP_FAULTS": "executor.work:kill:1"},
    )
    assert w.run_once() is True   # victim: child dies hard; worker survives
    victim = _row(store, dag_id, "victim")
    assert victim["status"] == TaskStatus.FAILED.value  # max_retries=0
    assert "died" in (victim["error"] or "")
    w.child_env = {}              # env faults re-arm per fresh child process
    assert w.run_once() is True   # the loop lives on and claims 'next'
    after = _row(store, dag_id, "next")
    assert after["status"] == TaskStatus.SUCCESS.value


def test_hard_death_consumes_retry_then_succeeds(store, tmp_path):
    dag_id = _submit(
        store,
        TaskSpec(name="flaky", executor="noop", args={}, max_retries=1),
    )
    w = Worker(
        store, name="iso-w", chips=0, workdir=str(tmp_path), isolate=True,
        load_jax_executors=False,
        child_env={"MLCOMP_FAULTS": "executor.work:kill:1"},
    )
    assert w.run_once() is True   # dies; requeued (1 retry)
    assert _row(store, dag_id, "flaky")["status"] == TaskStatus.QUEUED.value
    w.child_env = {}              # env faults re-arm per fresh child process
    assert w.run_once() is True   # retry attempt succeeds
    assert _row(store, dag_id, "flaky")["status"] == TaskStatus.SUCCESS.value


def test_chip_pinning_env(store, tmp_path):
    """A task taking a strict subset of the worker's chips sees only its
    chip ids in TPU_VISIBLE_CHIPS; MLCOMP_TPU_CHIP_IDS is always set."""
    out = tmp_path / "env.txt"
    dag_id = _submit(
        store,
        TaskSpec(
            name="pin",
            executor="shell",
            args={
                "command":
                f"echo \"ids=$MLCOMP_TPU_CHIP_IDS vis=$TPU_VISIBLE_CHIPS\""
                f" > {out}"
            },
            resources=ResourceSpec(chips=2),
        ),
    )
    w = Worker(store, name="iso-w", chips=4, workdir=str(tmp_path),
               isolate=True, load_jax_executors=False)
    assert w.run_once() is True
    assert _row(store, dag_id, "pin")["status"] == TaskStatus.SUCCESS.value
    assert out.read_text().strip() == "ids=0,1 vis=0,1"


def test_worker_parent_initialises_no_backend(store, tmp_path):
    """The worker parent loads the JAX executors (registration only) and
    must never initialise a backend: on a TPU host a parent that holds
    the chip starves every task child it spawns."""
    from jax._src import xla_bridge

    before = set(xla_bridge._backends)
    Worker(store, name="nojax-w", chips=1, workdir=str(tmp_path),
           isolate=True, load_jax_executors=True)
    assert set(xla_bridge._backends) == before


def test_stop_kills_running_child(store, tmp_path):
    """Stopping an in-progress task terminates its child instead of letting
    it compute to a discarded finish."""
    import threading

    marker = tmp_path / "finished.txt"
    dag_id = _submit(
        store,
        TaskSpec(
            name="long",
            executor="shell",
            args={"command": f"sleep 30 && touch {marker}"},
        ),
    )
    done = threading.Event()

    def run_worker():
        ws = Store(store.path)  # sqlite connections are thread-bound
        try:
            Worker(ws, name="iso-w", chips=0, workdir=str(tmp_path),
                   isolate=True, load_jax_executors=False).run_once()
        finally:
            ws.close()
            done.set()

    t = threading.Thread(target=run_worker, daemon=True)
    t.start()
    # wait for the task to go in_progress, then stop it
    own_store = Store(store.path)
    try:
        deadline = time.time() + 20
        tid = _row(store, dag_id, "long")["id"]
        while time.time() < deadline:
            r = own_store.task_row(tid)
            if r["status"] == TaskStatus.IN_PROGRESS.value:
                break
            time.sleep(0.1)
        else:
            pytest.fail("task never started")
        assert own_store.stop_task(tid)
        assert done.wait(timeout=20), "worker did not return after stop"
    finally:
        own_store.close()
    assert _row(store, dag_id, "long")["status"] == TaskStatus.STOPPED.value
    assert not marker.exists()


def test_concurrent_children_via_poll(store, tmp_path):
    """poll() packs two 1-chip tasks onto a 2-chip worker concurrently."""
    dag_id = _submit(
        store,
        TaskSpec(name="a", executor="shell",
                 args={"command": f"sleep 2 && echo a >> {tmp_path}/order"},
                 resources=ResourceSpec(chips=1)),
        TaskSpec(name="b", executor="shell",
                 args={"command": f"sleep 2 && echo b >> {tmp_path}/order"},
                 resources=ResourceSpec(chips=1)),
    )
    w = Worker(store, name="iso-w", chips=2, workdir=str(tmp_path),
               isolate=True, load_jax_executors=False)
    t0 = time.time()
    w.poll()
    assert len(w._children) == 2, "both tasks should spawn in one poll"
    deadline = time.time() + 60
    while time.time() < deadline:
        w.poll()
        statuses = {r["name"]: r["status"] for r in store.task_rows(dag_id)}
        if all(s == TaskStatus.SUCCESS.value for s in statuses.values()):
            break
        time.sleep(0.2)
    else:
        pytest.fail(f"tasks did not finish: {statuses}")
    # serial execution would need >= 2 sleeps of 2 s plus two interpreter
    # startups; concurrency keeps wall clock well under that
    assert time.time() - t0 < 25


def test_child_logs_reach_store_with_relative_paths(tmp_path, monkeypatch):
    """A worker given RELATIVE --db/--workdir (the CLI defaults) must
    still deliver its children's ctx.log/metric writes to the right
    store — the child runs with cwd=workdir, where a relative db path
    would silently open a fresh empty database (found by a real CLI
    drive; results rode the spec file so the bug only ate observability).
    """
    import os

    from mlcomp_tpu.dag.schema import DagSpec, TaskSpec, TaskStatus
    from mlcomp_tpu.db.store import Store
    from mlcomp_tpu.scheduler.worker import Worker

    monkeypatch.chdir(tmp_path)
    store = Store("rel.sqlite")  # deliberately relative
    try:
        helper = tmp_path / "src" / "rl_helper.py"
        helper.parent.mkdir()
        helper.write_text(
            "def check(ctx):\n"
            "    ctx.log('hello-from-child')\n"
            "    ctx.metric('m', 1.5, step=0)\n"
            "    return {'ok': True}\n"
        )
        dag = DagSpec(
            name="rel", project="t",
            tasks=(TaskSpec(name="a", executor="pyfunc", args={
                "target": "rl_helper:check",
                "code_src": str(helper.parent),
                "code_import": [],
            }),),
        )
        dag_id = store.submit_dag(dag)
        store.set_task_status(dag_id, ["a"], TaskStatus.QUEUED)
        w = Worker(store, name="rw", workdir="wk", isolate=True)  # relative
        assert w.run_once() is True
        tid = store.task_rows(dag_id)[0]["id"]
        row = store.task_row(tid)
        assert row["status"] == TaskStatus.SUCCESS.value, row["error"]
        logs = "\n".join(l["message"] for l in store.task_logs(tid))
        assert "hello-from-child" in logs
        assert [list(p) for p in store.metric_series(tid, "m")] == [[0, 1.5]]
        assert not os.path.exists(tmp_path / "wk" / "rel.sqlite"), (
            "child opened a parallel database"
        )
    finally:
        store.close()
