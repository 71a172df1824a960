"""Per-task child process: the executor isolation boundary.

The reference isolates tasks in per-GPU Docker containers; the TPU-native
equivalent is one OS process per task with env-pinned chip visibility.
The worker writes a spec JSON ({db, claim, workdir, process_id, ...}),
spawns ``python -m mlcomp_tpu.scheduler.child <spec>``, and reads the
result JSON back.  What the boundary buys:

- a segfaulting / OOM-killed / fault-injected executor takes down only
  this process — the worker loop reaps the corpse and routes the task
  into the normal retry machinery;
- chip pinning is real: the parent sets ``TPU_VISIBLE_CHIPS`` before
  exec, so concurrent tasks on one host each see only their chips;
- multi-host tasks get a fresh JAX runtime per attempt:
  ``init_distributed()`` (parallel/distributed.py) reads the
  MLCOMP_TPU_COORDINATOR / _NUM_PROCESSES / _PROCESS_ID env the worker
  sets from the gang row, and the whole distributed state dies with the
  process instead of wedging a long-lived worker.

Exit code 0 = executor returned; anything else (including death by
signal) = failure.  The result file is written atomically so the parent
never reads a half-written JSON.
"""

from __future__ import annotations

import json
import os
import sys
import traceback


def run_spec(spec_path: str) -> int:
    with open(spec_path) as f:
        spec = json.load(f)
    claim = spec["claim"]
    result_path = spec["result"]
    process_id = int(spec.get("process_id", 0))
    ok, result, err = False, None, None
    store = None
    try:
        # SIGTERM = preemption notice (spot TPU-VM reclaim, pool drain):
        # flag it so the train loop checkpoints and exits cleanly instead
        # of dying mid-step; the worker requeues preempted tasks without
        # consuming a retry (utils/preempt.py)
        from mlcomp_tpu.utils.preempt import install_signal_handler

        install_signal_handler()

        # distributed init must precede ANY jax use in executor code
        from mlcomp_tpu.parallel.distributed import init_distributed

        init_distributed()  # no-op unless the gang env is set

        from mlcomp_tpu import executors as _executors
        from mlcomp_tpu.db.store import Store
        from mlcomp_tpu.executors.base import ExecutionContext, run_task
        from mlcomp_tpu.scheduler.worker import sync_code

        _executors.load_all()
        store = Store(spec["db"])
        if os.environ.get("MLCOMP_TPU_COORDINATOR"):
            import jax

            store.log(
                claim["id"], "info",
                f"[slot {process_id}] jax distributed: "
                f"process_count={jax.process_count()} "
                f"process_index={jax.process_index()}",
            )
        args = json.loads(claim["args"])
        sync_code(args, claim["id"], spec["workdir"], store)
        ctx = ExecutionContext(
            dag_id=claim["dag_id"],
            task_id=claim["id"],
            task_name=claim["name"],
            args=args,
            store=store,
            workdir=spec["workdir"],
            chips=claim["chips"],
            stage=claim["stage"],
            primary=process_id == 0,
            worker=claim.get("worker"),
        )
        ok, result, err = run_task(claim["executor"], ctx)
    except Exception:
        err = traceback.format_exc()
    finally:
        if store is not None:
            try:
                if err and process_id != 0:
                    # slot>0 errors land in the shared task log (the task
                    # row itself is owned by slot 0)
                    store.log(
                        claim["id"], "error", f"[slot {process_id}] {err}"
                    )
                store.close()
            except Exception:
                pass
    tmp = result_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"ok": ok, "result": result, "error": err}, f)
    os.replace(tmp, result_path)
    return 0 if ok else 1


def main(argv=None) -> int:
    if argv is None:
        # process entry point: same compile cache as the worker's
        # other children (utils/compile_cache.py)
        from mlcomp_tpu.utils.compile_cache import place_compile_cache

        place_compile_cache()
        argv = sys.argv[1:]
    if len(argv) != 1:
        print("usage: python -m mlcomp_tpu.scheduler.child <spec.json>",
              file=sys.stderr)
        return 2
    return run_spec(argv[0])


if __name__ == "__main__":
    sys.exit(main())
