"""Sweep a closed-loop serve cell over ``prefill_chunk`` (and, with
``--slots``, over the slot count) on the chip: one process, one set-up
a setting, the cell's own traffic for ``--seconds`` each, no reference
pass.  Prints one JSON line a setting: tokens/s of requests completed
in the window (the cell's ``serve_tokens_per_s``), requests completed,
set-up seconds and the device's memory after the warm-up and after the
window.

    python tools/exp_chunk_sweep.py --workload mixed-length-offline \\
        --chunks 1024,2048,4096 --seconds 30 --seed 7

``--rehearsal 1`` runs the tiny CPU configuration (a rehearsal of the
script, not a measurement).  Nothing under ``benchmark/`` is touched.
"""
import argparse
import contextlib
import gc
import json
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--chunks", required=True)
    ap.add_argument("--slots", default="")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rehearsal", type=int, default=0)
    args = ap.parse_args()

    from benchmark import cells, serving
    from benchmark import device as D
    from benchmark.harness import configure_jax

    cell = cells.Cell(args.workload, rehearsal=bool(args.rehearsal))
    configure_jax(cell)
    D.describe(cell.chips, cell.rehearsal)
    quiet = lambda *a: None                                 # noqa: E731
    base = dict(cell.config["service"])
    slots = [int(s) for s in args.slots.split(",") if s] or [
        base["batch_sizes"][-1]]
    for chunk in (int(c) for c in args.chunks.split(",")):
        for n in slots:
            cell.config["service"] = {
                **base, "prefill_chunk": chunk, "batch_sizes": [n]}
            t0 = time.perf_counter()
            service = None
            try:
                service = serving.build_service(cell, args.seed, quiet)
                serving.warm(service, cell, args.seed, quiet)
            except Exception as e:  # what the chip refuses is a reading
                print(json.dumps({"prefill_chunk": chunk, "slots": n,
                                  "refused": repr(e)[:300]}), flush=True)
                if service is not None:
                    service.close()
                del service
                gc.collect()
                continue
            setup = time.perf_counter() - t0
            warm = D.memory(cell.chips)
            win = serving.closed_loop(
                service, cell, args.seed, args.seconds,
                int(cell.config["vocab_size"]),
                lambda name: contextlib.nullcontext())
            serving.drain(win["reqs"], 120.0)
            e2e = serving.reduce_window(win, args.seconds)
            eng = service.stats()["engine"]
            print(json.dumps({
                "prefill_chunk": chunk, "slots": n, "seconds": args.seconds,
                "serve_tokens_per_s": e2e["serve_tokens_per_s"],
                "completed_in_window": e2e["completed_in_window"],
                "sent": e2e["sent"], "failed": e2e["failed"],
                "setup_s": round(setup, 1),
                "memory_after_warm": warm,
                "memory_after_window": D.memory(cell.chips),
                "rows_attended_share":
                    eng["attention"]["rows_attended_share"],
                # since the process began, warm-up included: starved
                # rows, and the lane's own books (busy ms, what the
                # queue's head waited for)
                "rows_starved": eng["attention"].get("rows_starved"),
                "rows_total": eng["attention"]["rows_total"],
                "admission": eng.get("admission"),
                "pipeline_occupancy": eng["pipeline"].get("occupancy"),
            }), flush=True)
            service.close()
            del service
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
