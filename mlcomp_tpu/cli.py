"""Command-line entry point: ``mlcomp-tpu <command>``.

Mirrors the reference's CLI surface (``mlcomp dag <yaml>`` submit path,
supervisor/worker daemons, report UI — BASELINE.json:5).  Commands grow as
subsystems land; each subcommand imports lazily so ``validate`` works
without JAX.
"""

from __future__ import annotations

import argparse
import json
import sys


def _cmd_validate(args: argparse.Namespace) -> int:
    from mlcomp_tpu.dag import parse_dag, topo_sort

    dag = parse_dag(args.config)
    order = topo_sort(dag.tasks)
    print(f"dag {dag.name!r} (project {dag.project!r}): {len(dag.tasks)} tasks")
    for t in order:
        deps = f" <- {list(t.depends)}" if t.depends else ""
        print(f"  {t.name} [{t.executor}/{t.stage}] chips={t.resources.chips}{deps}")
    return 0


def _cmd_dag(args: argparse.Namespace) -> int:
    from mlcomp_tpu.scheduler.local import run_dag_local

    results = run_dag_local(
        args.config, workers=args.workers, db_path=args.db,
        workdir=args.workdir,
    )
    bad = {n: s.value for n, s in results.items() if s.value != "success"}
    print(json.dumps({n: s.value for n, s in results.items()}, indent=2))
    return 1 if bad else 0


def _cmd_submit(args: argparse.Namespace) -> int:
    from pathlib import Path

    from mlcomp_tpu.dag import parse_dag
    from mlcomp_tpu.db.store import Store
    from mlcomp_tpu.io.sync import inject_code_sync

    dag = parse_dag(args.config)
    dag = inject_code_sync(dag, base_dir=Path(args.config).parent)
    store = Store(args.db)
    dag_id = store.submit_dag(dag)
    store.close()
    print(
        json.dumps(
            {"dag_id": dag_id, "name": dag.name, "tasks": len(dag.tasks)}
        )
    )
    return 0


def _cmd_status(args: argparse.Namespace) -> int:
    from mlcomp_tpu.db.store import Store

    store = Store(args.db)
    try:
        dags = store.list_dags()
        if args.dag is not None:
            rows = store.task_rows(args.dag)
            for r in rows:
                line = f"  {r['id']:>4} {r['name']:<28} {r['status']:<12}"
                if r["worker"]:
                    line += f" worker={r['worker']}"
                if r["error"]:
                    line += f" error={r['error'].splitlines()[-1][:60]}"
                print(line)
            return 0
        for d in dags:
            counts: dict = {}
            for s in store.task_statuses(d["id"]).values():
                counts[s.value] = counts.get(s.value, 0) + 1
            print(
                f"{d['id']:>4} {d['name']:<20} {d['project']:<12}"
                f" {d['status']:<12} {counts}"
            )
        return 0
    finally:
        store.close()


def _dag_or_task(args: argparse.Namespace) -> bool:
    """stop/restart target validation: exactly one of DAG or --task."""
    if (args.dag is None) == (args.task is None):
        print("error: give either a DAG id or --task TASK_ID", file=sys.stderr)
        return False
    return True


def _cmd_stop(args: argparse.Namespace) -> int:
    from mlcomp_tpu.db.store import Store

    if not _dag_or_task(args):
        return 2
    store = Store(args.db)
    if args.task is not None:
        out = {"task_id": args.task, "stopped": store.stop_task(args.task)}
    else:
        out = {"dag_id": args.dag, "stopped_tasks": store.stop_dag(args.dag)}
    store.close()
    print(json.dumps(out))
    return 0


def _cmd_restart(args: argparse.Namespace) -> int:
    from mlcomp_tpu.db.store import Store

    if not _dag_or_task(args):
        return 2
    store = Store(args.db)
    if args.task is not None:
        out = {"task_id": args.task, "reset_tasks": store.restart_task(args.task)}
    else:
        out = {"dag_id": args.dag, "reset_tasks": store.restart_dag(args.dag)}
    store.close()
    print(json.dumps(out))
    return 0


def _cmd_supervisor(args: argparse.Namespace) -> int:
    from mlcomp_tpu.scheduler.supervisor import Supervisor
    from mlcomp_tpu.db.store import Store

    notifiers = None
    if args.notify:
        import yaml

        notifiers = [yaml.safe_load(n) for n in args.notify]
    sup = Supervisor(Store(args.db), notifiers=notifiers)
    sup.run_forever(poll_interval=args.poll)
    return 0


def _cmd_worker(args: argparse.Namespace) -> int:
    import signal
    import threading

    from mlcomp_tpu.scheduler.worker import Worker
    from mlcomp_tpu.db.store import Store

    w = Worker(
        Store(args.db),
        name=args.name,
        chips=args.chips,
        workdir=args.workdir,
        isolate=not args.in_process,
        max_tasks=args.max_tasks,
    )
    # SIGTERM drains: running tasks finish, nothing new is claimed, then
    # the loop returns — what `cli pool` sends on stop
    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *a: stop.set())
    w.run_forever(poll_interval=args.poll, stop_event=stop)
    return 0


def _cmd_pool(args: argparse.Namespace) -> int:
    from mlcomp_tpu.db.store import Store
    from mlcomp_tpu.scheduler.pool import WorkerPool, parse_inventory

    if bool(args.inventory) == bool(args.hosts):
        print("error: pass exactly one of --inventory / --hosts",
              file=sys.stderr)
        return 2
    if args.inventory:
        with open(args.inventory) as f:
            hosts = parse_inventory(f.read(), default_chips=args.chips)
    else:
        hosts = parse_inventory(
            "\n".join(h.strip() for h in args.hosts.split(",")),
            default_chips=args.chips,
        )
    pool = WorkerPool(
        Store(args.db),
        hosts,
        db_path=args.db,
        base_workdir=args.workdir,
        launch_template=args.launch,
        kill_template=args.kill,
        heartbeat_timeout_s=args.heartbeat_timeout,
    )
    pool.run_forever(poll_interval=args.poll)
    return 0


def _cmd_tokenize(args: argparse.Namespace) -> int:
    """Text corpus -> flat token .bin (+ .json sidecar) for ``token_bin``.

    Default encoding is BYTE-level (ids 0-255 + EOS 256 between
    documents): dependency-free, lossless on any UTF-8 text, and the
    standard small-scale baseline.  ``--hf-tokenizer PATH`` swaps in a
    local pretrained tokenizer directory via ``transformers`` (LOCAL
    path only — this environment has no network egress, and serving
    real vocabularies is the production path anyway).
    """
    from pathlib import Path

    import numpy as np

    out = Path(args.output)
    sidecar = out.with_suffix(out.suffix + ".json")

    def _keep(q: Path, root: Path) -> bool:
        # never re-ingest our own output (a second run over the same
        # directory would tokenize the .bin garbage into the corpus);
        # inside a scanned directory, skip hidden trees (.git and
        # friends) — judged only BELOW the user-given root, so roots
        # like ../corpus or ~/.cache/corpus still work when named
        # explicitly
        if q.resolve() in (out.resolve(), sidecar.resolve()):
            return False
        rel = q.relative_to(root).parts if root is not None else ()
        return not any(part.startswith(".") for part in rel)

    paths: list = []
    for src in args.inputs:
        p = Path(src)
        if p.is_dir():
            paths.extend(
                sorted(
                    q for q in p.rglob("*") if q.is_file() and _keep(q, p)
                )
            )
        elif p.exists():
            if _keep(p, None):
                paths.append(p)
        else:
            print(f"error: no such input {src!r}", file=sys.stderr)
            return 2
    if not paths:
        print("error: no input files", file=sys.stderr)
        return 2

    tok = None
    if args.hf_tokenizer:
        from transformers import AutoTokenizer  # local files only

        tok = AutoTokenizer.from_pretrained(
            args.hf_tokenizer, local_files_only=True
        )
        eos_id = tok.eos_token_id
        if eos_id is None:
            # first id past BOTH the base vocab and any added tokens —
            # tok.vocab_size excludes added ids and could alias one
            eos_id = len(tok)
        vocab_size = max(len(tok), eos_id + 1)
    else:
        eos_id = 256
        vocab_size = 257
    dtype = np.uint16 if vocab_size <= 65536 else np.uint32

    total = 0
    with open(out, "wb") as f:
        for p in paths:
            text = p.read_text(encoding="utf-8", errors="replace")
            if tok is not None:
                ids = tok.encode(text, add_special_tokens=False)
            else:
                ids = list(text.encode("utf-8"))
            ids.append(eos_id)
            np.asarray(ids, dtype=dtype).tofile(f)
            total += len(ids)
    meta = {
        "dtype": np.dtype(dtype).name,
        "vocab_size": int(vocab_size),
        "eos_id": int(eos_id),
        "tokens": int(total),
        "documents": len(paths),
        "tokenizer": args.hf_tokenizer or "byte",
    }
    sidecar.write_text(json.dumps(meta))
    print(json.dumps(meta))
    return 0


def _cmd_report(args: argparse.Namespace) -> int:
    from mlcomp_tpu.report.server import serve

    serve(db_path=args.db, host=args.host, port=args.port)
    return 0


def _cmd_average(args: argparse.Namespace) -> int:
    from mlcomp_tpu.io.checkpoint import average_checkpoints

    weights = None
    if args.weights:
        weights = [float(w) for w in args.weights.split(",")]
    path = average_checkpoints(args.sources, args.out, weights=weights)
    print(json.dumps({"averaged": len(args.sources), "out": path}))
    return 0


def _steps_per_dispatch(value: str):
    """argparse type for --steps-per-dispatch: an int pins K, the
    literal 'adaptive' selects the ladder controller (the default when
    the flag is absent)."""
    if value.strip().lower() == "adaptive":
        return "adaptive"
    try:
        return int(value)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected an integer or 'adaptive', got {value!r}"
        )


def _cmd_serve(args: argparse.Namespace) -> int:
    import yaml

    from mlcomp_tpu.serve import load_service, resolve_storage_ckpt, serve_http

    with open(args.model) as f:
        doc = yaml.safe_load(f)
    # accept either a bare model mapping or a DAG/train YAML with a
    # top-level ``model:`` anchor (the common case: point at the same
    # file you trained from)
    model_cfg = doc.get("model", doc) if isinstance(doc, dict) else doc
    if args.kv_quant:
        model_cfg = {**model_cfg, "kv_quant": True}
    if not args.ckpt and not args.storage_task:
        # serving random init silently would look healthy and emit junk
        print("error: pass --ckpt or --storage-task (a checkpoint to"
              " serve)", file=sys.stderr)
        return 2
    ckpt = args.ckpt
    if not ckpt:
        parts = args.storage_task.split("/")
        if len(parts) != 3:
            print(f"error: --storage-task must be PROJECT/DAG/TASK, got"
                  f" {args.storage_task!r}", file=sys.stderr)
            return 2
        ckpt = resolve_storage_ckpt(*parts)
    mesh_cfg = None
    if args.mesh:
        try:
            mesh_cfg = {
                k.strip(): int(v)
                for k, v in (kv.split("=") for kv in args.mesh.split(","))
            }
        except ValueError:
            print(f"error: --mesh expects AXIS=N[,AXIS=N...], got"
                  f" {args.mesh!r}", file=sys.stderr)
            return 2
    dist = None
    if args.distributed:
        # multi-host serve gang: connect this process to the
        # jax.distributed runtime FIRST (device discovery must see the
        # whole slice), then open the boundary side channel.  Every
        # process runs the identical command line; process 0 fronts
        # the gang, the rest follow (ready:false).
        if not mesh_cfg:
            print("error: --distributed needs --mesh (the gang runs "
                  "one SPMD program over the global device mesh)",
                  file=sys.stderr)
            return 2
        from mlcomp_tpu.parallel.distributed import (
            BoundaryChannel,
            init_distributed,
        )

        init_distributed()
        dist = BoundaryChannel(port=args.sync_port)
    slo_config = None
    if args.slo_config:
        if not args.metrics_history_interval:
            print("error: --slo-config needs the metrics-history "
                  "sampler; don't combine it with "
                  "--metrics-history-interval 0", file=sys.stderr)
            return 2
        try:
            with open(args.slo_config) as f:
                slo_config = json.load(f)
            # semantic validation HERE — before the expensive model
            # build/restore — so a bad config gets the same clean
            # error/exit-2 path a JSON syntax error does
            from mlcomp_tpu.obs.slo import validate_config

            validate_config(slo_config)
        except (OSError, ValueError) as e:
            print(f"error: --slo-config {args.slo_config!r}: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            return 2
    service = load_service(
        model_cfg,
        ckpt_dir=ckpt,
        mesh_cfg=mesh_cfg,
        batch_sizes=tuple(int(x) for x in args.batch_sizes.split(",")),
        prompt_buckets=tuple(int(x) for x in args.prompt_buckets.split(",")),
        max_new_buckets=tuple(
            int(x) for x in args.max_new_buckets.split(",")
        ),
        temperature=args.temperature,
        top_k=args.top_k,
        top_p=args.top_p,
        repetition_penalty=args.repetition_penalty,
        eos_id=args.eos_id,
        pad_id=args.pad_id,
        quantize=args.quantize or False,
        steps_per_dispatch=args.steps_per_dispatch,
        prefill_chunk=args.prefill_chunk,
        engine_fused_admission=(
            False if args.engine_staged_admission else None
        ),
        prefix_cache=args.prefix_cache,
        prefix_cache_bytes=args.prefix_cache_bytes,
        flight_recorder_events=args.flight_recorder_events,
        request_timeout_s=args.request_timeout,
        max_queue_depth=args.max_queue_depth,
        max_concurrent_requests=args.max_concurrent_requests,
        dispatch_stall_timeout=args.dispatch_stall_timeout or None,
        kv_layout=args.kv_layout,
        kv_page_tokens=args.kv_page_tokens,
        kv_pages=args.kv_pages,
        max_slots=args.max_slots,
        metrics_history_interval=args.metrics_history_interval,
        slo_config=slo_config,
        dist=dist,
        phase=args.phase,
    )
    if args.warmup:
        import time

        t0 = time.perf_counter()
        n = service.warmup()
        print(json.dumps({
            "event": "warmup", "programs": n,
            # compiles dominate: this is what a warm persistent compile
            # cache (utils/compile_cache.py) takes away
            "seconds": round(time.perf_counter() - t0, 3),
        }), flush=True)
    serve_http(
        service, host=args.host, port=args.port,
        model_name=str(model_cfg.get("name", "model")),
    )
    return 0


def _cmd_fleet(args: argparse.Namespace) -> int:
    """Run a managed replica fleet: N serve daemons reconciled by the
    ReplicaManager behind the prefix-affinity router, optionally
    autoscaled from SLO burn / reject-rate signals."""
    import os
    import threading
    import time

    from mlcomp_tpu.fleet import (
        Autoscaler,
        AutoscalePolicy,
        ReplicaManager,
        ReplicaSpec,
        Router,
        SchedulerLauncher,
        SubprocessLauncher,
        make_router_http_server,
    )
    from mlcomp_tpu.obs.metrics import Registry

    if not args.ckpt and not args.storage_task:
        print("error: pass --ckpt or --storage-task (a checkpoint to"
              " serve)", file=sys.stderr)
        return 2
    try:
        lo, hi = (int(x) for x in args.port_range.split(":"))
    except ValueError:
        print(f"error: --port-range expects LO:HI, got"
              f" {args.port_range!r}", file=sys.stderr)
        return 2
    registry_path = os.path.abspath(args.registry)
    max_replicas = args.max_replicas or max(
        args.replicas, args.min_replicas
    )
    phase_split = None
    if args.phase_split:
        if args.scheduler or args.autoscale or args.autoscale_dry_run:
            print("error: --phase-split does not combine with"
                  " --scheduler or --autoscale yet (a phase-split"
                  " fleet runs two fixed replica sets)",
                  file=sys.stderr)
            return 2
        try:
            n_prefill, n_decode = (
                int(x) for x in args.phase_split.split(":")
            )
            if n_prefill < 1 or n_decode < 1:
                raise ValueError
        except ValueError:
            print(f"error: --phase-split expects P:D with both >= 1,"
                  f" got {args.phase_split!r}", file=sys.stderr)
            return 2
        phase_split = (n_prefill, n_decode)
    if args.scheduler:
        import yaml

        from mlcomp_tpu.db.store import Store

        with open(args.model) as f:
            doc = yaml.safe_load(f)
        model_cfg = doc.get("model", doc) if isinstance(doc, dict) else doc
        launcher = SchedulerLauncher(
            Store(args.db), model_cfg, registry_path,
            serve_args={
                # --storage-task resolves ON THE WORKER (ModelStorage
                # layouts are per-host); only an explicit --ckpt path
                # is forwarded verbatim
                "ckpt": args.ckpt,
                "storage_task": args.storage_task,
                "host": "auto", "warmup": True,
            },
            chips=args.chips,
        )
        port_range = None  # replicas bind ephemeral ports on their host
    else:
        serve_argv = ["--model", args.model]
        if args.ckpt:
            serve_argv += ["--ckpt", args.ckpt]
        else:
            serve_argv += ["--storage-task", args.storage_task]
        serve_argv += ["--warmup"]
        for extra in args.serve_arg:
            serve_argv += extra.split()
        launcher = SubprocessLauncher(
            serve_argv, host=args.host, log_dir=args.log_dir,
            chips=args.chips, port_base=lo,
        )
        port_range = (lo, hi)
    metrics = Registry()
    if phase_split is not None:
        if args.scheduler:
            raise AssertionError  # rejected above
        n_prefill, n_decode = phase_split
        # split the port window between the sets (each manager tracks
        # its own used ports) and force the role flags AFTER the
        # user's --serve-arg extras, so argparse last-wins keeps the
        # sets coherent: prefill daemons run the dense admission core,
        # decode daemons the paged slot loop
        mid = lo + (hi - lo) // 2

        def strip_flags(argv, flags):
            """Drop ``--flag value`` pairs the prefill daemons reject
            (decode-pool tuning passed via --serve-arg sizes
            the DECODE half; a prefill_only engine refuses them at
            construction, which would crash-loop the whole set)."""
            out, skip = [], False
            for a in argv:
                if skip:
                    skip = False
                    continue
                if a in flags:
                    skip = True
                    continue
                out.append(a)
            return out

        decode_only = ("--kv-pages", "--max-slots")
        managers = []
        for set_name, target, prange, base_argv, extra in (
            ("prefill", n_prefill, (lo, mid),
             strip_flags(serve_argv, decode_only),
             ["--phase", "prefill", "--kv-layout", "dense"]),
            ("decode", n_decode, (mid + 1, hi), serve_argv,
             ["--phase", "decode", "--kv-layout", "paged"]),
        ):
            managers.append(ReplicaManager(
                SubprocessLauncher(
                    base_argv + extra, host=args.host,
                    log_dir=args.log_dir, chips=args.chips,
                    port_base=prange[0],
                    # the decode set's chips start after the prefill
                    # set's
                    chip_offset=(
                        0 if set_name == "prefill"
                        else n_prefill * args.chips
                    ),
                ),
                ReplicaSpec(
                    target=target,
                    set_name=set_name,
                    phase=extra[1],
                    port_range=prange,
                    health_poll_s=args.health_poll,
                    restart_budget=args.restart_budget,
                ),
                # the per-set managers would fight over the fleet-wide
                # replicas_target/live gauges (one unlabeled gauge,
                # two writers): the ROUTER's live_by_phase gauge is
                # the per-phase observability surface instead
                metrics=None,
                registry_path=registry_path,
            ))
    else:
        managers = [ReplicaManager(
            launcher,
            ReplicaSpec(
                target=args.replicas,
                port_range=port_range,
                health_poll_s=args.health_poll,
                restart_budget=args.restart_budget,
            ),
            metrics=metrics,
            registry_path=registry_path,
        )]
    manager = managers[0]
    router = Router(
        manager=managers if len(managers) > 1 else manager,
        metrics=metrics,
        health_poll_s=min(args.health_poll, 1.0),
    )
    scaler = None
    stop = threading.Event()
    threads = []
    if args.autoscale or args.autoscale_dry_run:
        scaler = Autoscaler(
            AutoscalePolicy(
                min_replicas=args.min_replicas,
                max_replicas=max_replicas,
            ),
            manager=manager,
            metrics=metrics,
            dry_run=args.autoscale_dry_run,
        )

        def scale_loop():
            while not stop.wait(args.autoscale_interval):
                try:
                    d = scaler.run_tick()
                    if d["direction"] != "hold":
                        print(json.dumps(
                            {"event": "autoscale", **d}
                        ), flush=True)
                except Exception as e:
                    print(json.dumps({
                        "event": "autoscale_error", "error": str(e),
                    }), flush=True)

        threads.append(threading.Thread(target=scale_loop, daemon=True))
    for m in managers:
        m.start()
    router.start()
    httpd = make_router_http_server(router, args.host, args.port)
    for t in threads:
        t.start()
    print(json.dumps({
        "event": "fleet", "router": f"http://{args.host}:{args.port}",
        "registry": registry_path,
        "replicas": (
            sum(phase_split) if phase_split else args.replicas
        ),
        "phase_split": (
            f"{phase_split[0]}:{phase_split[1]}" if phase_split
            else None
        ),
        "autoscale": bool(scaler),
        "dry_run": bool(scaler and scaler.dry_run),
    }), flush=True)
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        stop.set()
        httpd.shutdown()
        httpd.server_close()
        router.close()
        for m in managers:
            m.close(stop_replicas=True)
        # give subprocess replicas a beat to die before the registry
        # file is left behind as state for the next incarnation
        time.sleep(0.1)
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="mlcomp-tpu", description=__doc__)
    sub = p.add_subparsers(dest="cmd", required=True)

    v = sub.add_parser("validate", help="parse + validate a DAG YAML")
    v.add_argument("config")
    v.set_defaults(fn=_cmd_validate)

    d = sub.add_parser("dag", help="run a DAG locally (in-process scheduler)")
    d.add_argument("config")
    d.add_argument("--workers", type=int, default=1)
    d.add_argument(
        "--db", default=None,
        help="persist the run's store here (default: a temp dir) so"
        " `status` and the report server can read it afterwards",
    )
    d.add_argument("--workdir", default=".")
    d.set_defaults(fn=_cmd_dag)

    sb = sub.add_parser("submit", help="submit a DAG to the queue (daemons run it)")
    sb.add_argument("config")
    sb.add_argument("--db", default="mlcomp.sqlite")
    sb.set_defaults(fn=_cmd_submit)

    st = sub.add_parser("status", help="list DAGs, or tasks of one DAG")
    st.add_argument("dag", nargs="?", type=int, default=None)
    st.add_argument("--db", default="mlcomp.sqlite")
    st.set_defaults(fn=_cmd_status)

    sp = sub.add_parser(
        "stop", help="stop a DAG (unfinished tasks -> stopped) or one --task"
    )
    sp.add_argument("dag", nargs="?", type=int, default=None)
    sp.add_argument("--task", type=int, default=None, help="stop one task by id")
    sp.add_argument("--db", default="mlcomp.sqlite")
    sp.set_defaults(fn=_cmd_stop)

    rs = sub.add_parser(
        "restart", help="re-run a DAG's unsuccessful tasks, or one --task"
    )
    rs.add_argument("dag", nargs="?", type=int, default=None)
    rs.add_argument(
        "--task", type=int, default=None,
        help="re-run one finished task (plus its skipped dependents)",
    )
    rs.add_argument("--db", default="mlcomp.sqlite")
    rs.set_defaults(fn=_cmd_restart)

    s = sub.add_parser("supervisor", help="run the supervisor daemon")
    s.add_argument("--db", default="mlcomp.sqlite")
    s.add_argument("--poll", type=float, default=1.0)
    s.add_argument(
        "--notify",
        action="append",
        metavar="YAML",
        help='notifier spec, e.g. \'{type: file, path: events.jsonl}\' (repeatable)',
    )
    s.set_defaults(fn=_cmd_supervisor)

    w = sub.add_parser("worker", help="run a worker daemon")
    w.add_argument("--db", default="mlcomp.sqlite")
    w.add_argument("--name", default=None)
    w.add_argument("--chips", type=int, default=0)
    w.add_argument("--poll", type=float, default=0.5)
    w.add_argument("--workdir", default=".")
    w.add_argument(
        "--in-process",
        action="store_true",
        help="run executors inside the worker process instead of isolated"
        " per-task children (no crash isolation, no chip pinning, no"
        " multi-host gangs; mainly for debugging)",
    )
    w.add_argument(
        "--max-tasks",
        type=int,
        default=None,
        help="max concurrent isolated tasks (default: max(1, chips))",
    )
    w.set_defaults(fn=_cmd_worker)

    pl = sub.add_parser(
        "pool",
        help="provision worker daemons over a host inventory and keep"
        " them alive (launch, heartbeat-watch, restart, drain on stop)",
    )
    pl.add_argument("--db", default="mlcomp.sqlite")
    pl.add_argument(
        "--inventory", default=None,
        help="inventory file: one host per line, optional chips=N"
        " workdir=PATH attrs; # comments",
    )
    pl.add_argument(
        "--hosts", default=None,
        help="inline inventory, comma-separated hosts (e.g."
        " localhost,tpu-vm-0)",
    )
    pl.add_argument("--chips", type=int, default=0,
                    help="default chips per host")
    pl.add_argument("--workdir", default="pool",
                    help="base dir for per-worker workdirs and logs")
    pl.add_argument(
        "--launch", default=None,
        help="launch template override; placeholders {host} {python} {db}"
        " {name} {chips} {workdir} (default: direct exec for localhost,"
        " ssh -o BatchMode=yes for remote hosts)",
    )
    pl.add_argument(
        "--kill", default=None,
        help="kill template override (same placeholders plus {signal}):"
        " how to reach a wedged daemon on its host — for remote hosts"
        " the local handle is only the ssh transport, so the default"
        " remote template pkills the worker by name over a fresh ssh",
    )
    pl.add_argument("--heartbeat-timeout", type=float, default=30.0)
    pl.add_argument("--poll", type=float, default=2.0)
    pl.set_defaults(fn=_cmd_pool)

    tk = sub.add_parser(
        "tokenize",
        help="text corpus -> token .bin for the token_bin dataset"
        " (byte-level default; --hf-tokenizer for a local vocab)",
    )
    tk.add_argument("inputs", nargs="+", help="text files or directories")
    tk.add_argument("-o", "--output", required=True, help="output .bin path")
    tk.add_argument(
        "--hf-tokenizer", default=None,
        help="LOCAL pretrained tokenizer directory (transformers);"
        " default is byte-level (vocab 257, EOS 256)",
    )
    tk.set_defaults(fn=_cmd_tokenize)

    r = sub.add_parser("report", help="run the report/UI HTTP server")
    r.add_argument("--db", default="mlcomp.sqlite")
    r.add_argument("--host", default="127.0.0.1")
    r.add_argument("--port", type=int, default=8765)
    r.set_defaults(fn=_cmd_report)

    av = sub.add_parser(
        "average",
        help="weight-space average of checkpoints (SWA / model soup);"
        " saves a weights-only checkpoint restorable by eval/infer/serve",
    )
    av.add_argument("sources", nargs="+", metavar="DIR[:STEP]",
                    help="checkpoint dirs (latest step unless :STEP given)")
    av.add_argument("--out", required=True, help="output checkpoint dir")
    av.add_argument("--weights", default=None,
                    help="comma-separated per-source weights (normalized)")
    av.set_defaults(fn=_cmd_average)

    sv = sub.add_parser(
        "serve",
        help="serve an LM checkpoint over HTTP: KV-cache decode,"
        " micro-batched, bucketed static shapes (POST /generate)",
    )
    sv.add_argument(
        "--model", required=True,
        help="YAML with the model config (a bare mapping, or any DAG"
        " YAML with a top-level 'model:' section)",
    )
    sv.add_argument("--ckpt", default=None, help="checkpoint directory")
    sv.add_argument(
        "--storage-task", default=None, metavar="PROJECT/DAG/TASK",
        help="resolve the checkpoint from ModelStorage instead of --ckpt",
    )
    sv.add_argument("--host", default="127.0.0.1")
    sv.add_argument("--port", type=int, default=8900)
    sv.add_argument("--batch-sizes", default="1,2,4,8")
    sv.add_argument("--prompt-buckets", default="128,256,512,1024")
    sv.add_argument("--max-new-buckets", default="32,128")
    sv.add_argument("--temperature", type=float, default=0.0)
    sv.add_argument("--top-k", type=int, default=None)
    sv.add_argument("--top-p", type=float, default=None)
    sv.add_argument("--repetition-penalty", type=float, default=1.0)
    sv.add_argument("--eos-id", type=int, default=None)
    sv.add_argument("--pad-id", type=int, default=0)
    sv.add_argument(
        "--quantize", default=None, choices=("int8", "kernel"),
        help="int8 weight-only: storage ('int8', entry dequant) or the"
        " Pallas kernel path ('kernel', best at B=1)",
    )
    sv.add_argument(
        "--mesh", default=None, metavar="AXIS=N[,AXIS=N...]",
        help="serve SHARDED over a device mesh: Megatron tp weight"
        " layout, SPMD decode — for models too big for one chip."
        " Devices not claimed by named axes fold into dp (e.g."
        " 'tp=4' on 8 chips gives dp=2 tp=4), and every --batch-sizes"
        " entry must divide dp*fsdp — pass 'dp=1,tp=8' to keep small"
        " batches servable.  --quantize kernel and --kv-quant compose"
        " with tp/dp meshes (shard_map kernel islands); fsdp does not."
        " The continuous engine's dispatch pipeline (depth 2) and the"
        " paged KV layout compose with the mesh too; --prefix-cache"
        " remains single-chip",
    )
    sv.add_argument(
        "--distributed", action="store_true",
        help="multi-HOST serving: connect to the jax.distributed"
        " runtime (MLCOMP_TPU_COORDINATOR / _NUM_PROCESSES /"
        " _PROCESS_ID; under TPU auto-discovery still set"
        " MLCOMP_TPU_COORDINATOR — followers dial that host for the"
        " boundary side channel) and run one SPMD serve"
        " gang over the global --mesh.  Process 0 owns the HTTP front"
        " door and submit queue and broadcasts per-boundary"
        " admission/retire decisions over a TCP side channel"
        " (--sync-port) so every process executes the identical"
        " dispatch sequence; the other processes answer /healthz as"
        " ready:false followers (route traffic at the coordinator)."
        " Every process runs the SAME command line (same --mesh, same"
        " knobs, same seed)",
    )
    sv.add_argument(
        "--sync-port", type=int, default=None,
        help="--distributed boundary-channel TCP port (default:"
        " MLCOMP_TPU_SYNC_PORT, else the jax.distributed coordinator"
        " port + 1)",
    )
    sv.add_argument(
        "--steps-per-dispatch", type=_steps_per_dispatch, default=None,
        help="decode steps per compiled dispatch"
        " (K) — one host dispatch per K tokens; joins land at dispatch"
        " boundaries, so K bounds the extra join latency.  Default"
        " 'adaptive': the drive loop picks K per boundary from the"
        " live queue-depth/occupancy signals over a warmed 1/2/4/8"
        " ladder (shallow queues small K for TTFT, deep queues large K"
        " for amortization; tokens are bit-identical under any K"
        " schedule).  An integer PINS K — the bisect override",
    )
    sv.add_argument(
        "--engine-staged-admission", action="store_true",
        help="force the STAGED admission path —"
        " every prefill chunk runs as its own dispatch at a drained"
        " pipeline boundary (the pre-fused behavior; bisect/debug"
        " mode, outputs bit-identical).  Default: a pending"
        " admission's chunk rides the decode dispatch as one fused"
        " program, so decode never pauses for a prefill",
    )
    sv.add_argument(
        "--prefix-cache", action="store_true",
        help="host-RAM prefix KV cache (single-chip): requests"
        " sharing a cached prompt prefix fetch"
        " its K/V rows from host memory and prefill only the uncached"
        " suffix; responses carry cache_hit_tokens and GET"
        " /cache/stats reports hit/miss/eviction counters",
    )
    sv.add_argument(
        "--prefix-cache-bytes", type=int, default=1 << 31,
        help="host-byte budget for --prefix-cache (default 2 GiB);"
        " LRU-evicts unpinned prefixes beyond it",
    )
    sv.add_argument(
        "--prefill-chunk", type=int, default=256,
        help="admission prefill chunk (tokens) —"
        " a joiner prefills one chunk per dispatch boundary (fused"
        " into the decode dispatch by default); all-pad chunks are"
        " skipped",
    )
    sv.add_argument(
        "--kv-layout", default="dense", choices=("dense", "paged"),
        help="device KV layout. 'paged' stores KV"
        " as fixed-size pages gathered through per-slot page tables"
        " (mlcomp_tpu/kvpool): sequence length is paid per page,"
        " admission is gated by FREE PAGES instead of worst-case slot"
        " reservations (429 reason no_free_pages), the slot count"
        " scales elastically up to --max-slots, and same-placement"
        " shared prompt prefixes map the same physical pages"
        " copy-on-write.  Outputs are bit-identical to 'dense' (the"
        " default and the bisect mode).  Composes with --mesh: page"
        " arrays shard over tp at the kv-head axis, page tables"
        " replicate (MLCOMP_TPU_PAGED_ATTN=lax is the sharded"
        " reference/bisect path)",
    )
    sv.add_argument(
        "--kv-page-tokens", type=int, default=None,
        help="paged KV: tokens per page (default: the gcd of the"
        " buckets' prefill chunk widths, so chunk-aligned prefix"
        " boundaries land on page boundaries; must divide every"
        " bucket's chunk width)",
    )
    sv.add_argument(
        "--kv-pages", type=int, default=None,
        help="paged KV: total physical pages incl. the 2 reserved"
        " (default: the dense layout's KV bytes — equal HBM, paid per"
        " page, so mixed-length traffic fits more streams)",
    )
    sv.add_argument(
        "--max-slots", type=int, default=None,
        help="paged KV: elastic slot-count cap (default 4x the largest"
        " --batch-sizes entry); the live count grows under queued"
        " traffic when the page budget allows and shrinks back at"
        " quiesce",
    )
    sv.add_argument(
        "--kv-quant", action="store_true",
        help="int8 KV cache (Pallas flash-decode): halves the dominant"
        " HBM stream of batched/long-context decode",
    )
    sv.add_argument(
        "--flight-recorder-events", type=int, default=32768,
        help="bound on the engine flight recorder's"
        " event ring (GET /trace exports it as Perfetto-loadable Chrome"
        " trace JSON; GET /metrics is always on).  0 disables recording"
        " (its overhead is a dict append per event; not measured on the"
        " chip)",
    )
    sv.add_argument(
        "--request-timeout", type=float, default=600.0,
        help="per-request wall-clock budget in seconds (default 600,"
        " the old hardcoded future timeout): every request gets this"
        " as its default deadline, enforced by the engine at dispatch"
        " boundaries — expired requests free their slot and fail with"
        " 504.  Clients may pass a tighter \"deadline_s\" per request"
        " (larger values clamp to this budget — a slot is shared)",
    )
    sv.add_argument(
        "--max-queue-depth", type=int, default=0,
        help="bound on requests waiting for a slot"
        " — past it submits fast-fail with 429 + Retry-After derived"
        " from live per-token latency, instead of queueing unboundedly"
        " (0 = unbounded, the historical behavior)",
    )
    sv.add_argument(
        "--max-concurrent-requests", type=int, default=0,
        help="bound on total in-flight requests"
        " (queued + decoding); past it submits fast-fail with 429"
        " (0 = unbounded)",
    )
    sv.add_argument(
        "--dispatch-stall-timeout", type=float, default=300.0,
        help="watchdog threshold in seconds — a"
        " dispatch stuck in the runtime longer than this fails the"
        " in-flight requests, flips /healthz to 503, and (once the"
        " drive loop is provably dead) attempts one bounded restart."
        " Set well above your slowest legitimate dispatch (compile"
        " stalls count!); 0 disables the watchdog",
    )
    sv.add_argument(
        "--metrics-history-interval", type=float, default=5.0,
        help="seconds between metrics-history snapshots (the bounded"
        " ring behind GET /metrics/history and the SLO engine's burn"
        " rates; default 5).  0 disables the sampler — /metrics/history"
        " and /slo answer 404",
    )
    sv.add_argument(
        "--slo-config", default=None, metavar="FILE.json",
        help="JSON file overriding the default SLOs (TTFT p95,"
        " per-token p50, reject rate, engine-healthy uptime) and their"
        " windows/budgets — see docs/observability.md 'SLOs and burn"
        " rates'.  Malformed config fails startup, not the first"
        " evaluation",
    )
    sv.add_argument(
        "--phase", choices=("both", "prefill", "decode"),
        default="both",
        help="disaggregated serving role (docs/serving.md"
        " 'Disaggregated serving'): 'prefill' runs the admission core"
        " only and answers POST /prefill with KV-page handoff blobs"
        " (dense layout); 'decode' is a paged"
        " daemon that additionally admits handoffs via POST /import,"
        " skipping prefill with bit-identical tokens; 'both' (default)"
        " is the monolithic daemon",
    )
    sv.add_argument("--warmup", action="store_true",
                    help="precompile the hot buckets before listening")
    sv.set_defaults(fn=_cmd_serve)

    fl = sub.add_parser(
        "fleet",
        help="run a MANAGED replica fleet: N serve daemons reconciled"
        " by the ReplicaManager (spawn, health-poll, bounded restart,"
        " drain-on-scale-down) behind the prefix-affinity router, with"
        " optional SLO-burn/reject-rate autoscaling"
        " (docs/serving.md 'Running a fleet')",
    )
    fl.add_argument("--model", required=True,
                    help="model YAML (same file `serve` takes)")
    fl.add_argument("--ckpt", default=None, help="checkpoint directory")
    fl.add_argument(
        "--storage-task", default=None, metavar="PROJECT/DAG/TASK",
        help="resolve the checkpoint from ModelStorage instead of"
        " --ckpt",
    )
    fl.add_argument("--replicas", type=int, default=2,
                    help="initial replica target count")
    fl.add_argument("--min-replicas", type=int, default=1,
                    help="autoscaler floor")
    fl.add_argument("--max-replicas", type=int, default=None,
                    help="autoscaler ceiling (default: --replicas)")
    fl.add_argument(
        "--port-range", default="8901:8999", metavar="LO:HI",
        help="ports replicas are assigned from (subprocess launcher)",
    )
    fl.add_argument("--host", default="127.0.0.1",
                    help="router bind host (replicas bind it too)")
    fl.add_argument("--port", type=int, default=8900,
                    help="router port — clients POST /generate here")
    fl.add_argument(
        "--registry", default="fleet-registry.json",
        help="JSON replica registry file the manager maintains; point"
        " the report server at it via MLCOMP_TPU_SERVE_REGISTRY for"
        " live /fleet/trace + /fleet/metrics",
    )
    fl.add_argument("--health-poll", type=float, default=1.0,
                    help="seconds between replica /healthz polls")
    fl.add_argument(
        "--restart-budget", type=int, default=3,
        help="restarts per replica before the manager gives up on it"
        " (refilled by sustained health — progress-gated like the"
        " engine watchdog's own restart)",
    )
    fl.add_argument("--autoscale", action="store_true",
                    help="drive the target count from SLO burn rates"
                    " and admission-control reject ratios")
    fl.add_argument(
        "--autoscale-dry-run", action="store_true",
        help="compute, log, and count autoscale decisions WITHOUT"
        " applying them — stage the policy before handing it the lever",
    )
    fl.add_argument("--autoscale-interval", type=float, default=15.0,
                    help="seconds between autoscaler scrape+decide"
                    " ticks")
    fl.add_argument(
        "--scheduler", action="store_true",
        help="launch replicas as long-lived scheduler tasks through"
        " the --db store (any worker with the chips runs one; the"
        " Supervisor requeues replicas whose worker dies) instead of"
        " local child processes",
    )
    fl.add_argument("--db", default="mlcomp.sqlite",
                    help="store for --scheduler mode")
    fl.add_argument(
        "--chips", type=int, default=0,
        help="chips per replica.  --scheduler mode: the replica task's"
        " chip claim.  Subprocess launcher: replica k (by port slot)"
        " is pinned to chips [k*N, (k+1)*N) of this host so replicas"
        " never race for a chip; 0 pins nothing (CPU hosts)",
    )
    fl.add_argument(
        "--serve-arg", action="append", default=[],
        help="extra flag(s) appended to each replica's `serve` command"
        " (repeatable; subprocess launcher only), e.g."
        " --serve-arg '--prefix-cache'",
    )
    fl.add_argument("--log-dir", default=None,
                    help="per-replica stdout/stderr logs (subprocess"
                    " launcher)")
    fl.add_argument(
        "--phase-split", default=None, metavar="P:D",
        help="run a DISAGGREGATED fleet instead of N monolithic"
        " replicas: P prefill replicas (admission core only, POST"
        " /prefill hands back KV-page blobs) and D decode replicas"
        " (paged daemons admitting POST /import), with the router"
        " brokering the two-hop handoff per request"
        " (docs/serving.md 'Disaggregated serving').  Overrides"
        " --replicas; not combinable with --autoscale or --scheduler"
        " (named follow-ups)",
    )
    fl.set_defaults(fn=_cmd_fleet)

    args = p.parse_args(argv)
    if argv is None:
        # a process entry point (console script / python -m), not an
        # in-process call: place the persistent compile cache before
        # any subcommand imports JAX
        from mlcomp_tpu.utils.compile_cache import place_compile_cache

        place_compile_cache()
    from mlcomp_tpu.dag.graph import DagValidationError
    from mlcomp_tpu.utils.config import ConfigError

    try:
        return args.fn(args)
    except (DagValidationError, ConfigError) as e:
        # user config errors: one clear line, no traceback
        print(f"error: {e}", file=sys.stderr)
        return 2
    except KeyboardInterrupt:
        return 130


if __name__ == "__main__":
    sys.exit(main())
