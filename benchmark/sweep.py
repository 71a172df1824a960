"""The knee sweep of an open-loop cell: one set-up, then the cell's mix at
each of a few fixed rates, one window each.  Run once, when a cell is
defined; the rate the cell then offers is fixed in its workload file at
about four fifths of the knee.  Not part of a benchmark run.

    python3 -m benchmark.sweep --workload chat-steady --seed 7 \\
        --seconds 25 --rates 2,3,4,5,6,7

Every rate offers the cell's own trace (the mix's ``schedule_seed``, its
gaps scaled to the rate), so the knee is read on the generator the cell
runs.  For each rate it prints the tails, the share of requests inside
both latency limits (the mix's ``latency_limits_ms``; a failed request
misses; a mix that has none yet counts every finished request, for the
first sweep that sets them), and the backlog: requests still waiting
for a first token at the window's half and at its close.  The knee is
the highest rate at which >= 90% meet both limits with no backlog
growing through the window.
"""

from __future__ import annotations

import argparse
import contextlib
import time


def main() -> None:
    from benchmark import cells, serving
    from benchmark import device as D
    from benchmark.harness import LowerCounter, configure_jax, log

    ap = argparse.ArgumentParser(prog="benchmark.sweep")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--rehearsal", type=int, default=0)
    args = ap.parse_args()
    cell = cells.Cell(args.workload, rehearsal=bool(args.rehearsal))
    configure_jax(cell)
    dev = D.describe(cell.chips, cell.rehearsal)
    log("device", {k: dev[k] for k in ("platform", "kind", "count")})
    counter = LowerCounter()
    service = serving.build_service(cell, args.seed, log)
    serving.warm(service, cell, args.seed, log)
    vocab = int(cell.config["vocab_size"])
    limits = cell.traffic.get("latency_limits_ms", {})
    ttft_ms = float(limits.get("ttft", float("inf")))
    tpot_ms = float(limits.get("tpot", float("inf")))
    log("sweep.latency_limits_ms", {"ttft": ttft_ms, "tpot": tpot_ms})
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell.traffic["rate_per_s"] = rate
        with counter.window():
            win = serving.open_loop(
                service, cell, args.seed + i, args.seconds, vocab,
                lambda name: contextlib.nullcontext(),
            )
            lowered = counter.n
        t0, t_end = win["t0"], win["t_end"]
        mid = t0 + 0.5 * args.seconds

        def waiting(at):
            return sum(
                1 for r in win["reqs"]
                if r.due + t0 <= at and (r.stream.t_first is None
                                         or r.stream.t_first > at)
            )

        backlog = {"at_half": waiting(mid), "at_close": waiting(t_end)}
        serving.drain(win["reqs"], 120.0)
        out = serving.reduce_window(win, args.seconds)
        met = 0
        for r in win["reqs"]:
            if r.result is None:
                continue
            n = len(r.result["ids"])
            ttft = (r.stream.t_first - t0 - r.due) * 1e3
            tpot = ((r.stream.t_last - r.stream.t_first) * 1e3 / (n - 1)
                    if n > 1 else 0.0)
            if ttft <= ttft_ms and tpot <= tpot_ms:
                met += 1
        log("sweep", {
            "rate_per_s": rate, "sent": out["sent"],
            "succeeded": out["succeeded"], "failed": out["failed"],
            "ttft_p50_ms": out.get("ttft_p50_ms"),
            "ttft_p90_ms": out.get("ttft_p90_ms"),
            "tpot_p50_ms": out.get("tpot_p50_ms"),
            "tpot_p90_ms": out.get("tpot_p90_ms"),
            "met_both_limits": met / max(1, out["sent"]),
            "backlog": backlog, "lateness": out["lateness"],
            "programs_lowered": lowered,
            "drain_s": time.perf_counter() - t_end,
        })
    service.close()


if __name__ == "__main__":
    main()
