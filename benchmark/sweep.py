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
misses; a mix that has none yet counts every finished request), and the
backlog: requests still waiting for a first token at the window's half
and at its close.  The knee is the highest rate at which >= 90% meet
both limits with no backlog growing through the window.

A cell's limits are twice the lowest rate's p90s, so the last line,
``sweep.against_twice_the_lowest_rate``, gives those limits and each
rate's share inside them: the first sweep of a new mix, and the sweep
that finds a knee again after the program got faster, read the knee
from one call.
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
    def inside(pairs, sent, ttft_ms, tpot_ms):
        """Share of the requests sent that finished inside both limits."""
        return sum(1 for a, b in pairs
                   if a <= ttft_ms and b <= tpot_ms) / max(1, sent)

    tails = []  # per rate: (rate, its numbers, (ttft, tpot) of each finished one)
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
        pairs = []
        for r in win["reqs"]:
            if r.result is None:
                continue
            n = len(r.result["ids"])
            ttft = (r.stream.t_first - t0 - r.due) * 1e3
            tpot = ((r.stream.t_last - r.stream.t_first) * 1e3 / (n - 1)
                    if n > 1 else 0.0)
            pairs.append((ttft, tpot))
        tails.append((rate, out, pairs))
        log("sweep", {
            "rate_per_s": rate, "sent": out["sent"],
            "succeeded": out["succeeded"], "failed": out["failed"],
            "ttft_p50_ms": out.get("ttft_p50_ms"),
            "ttft_p90_ms": out.get("ttft_p90_ms"),
            "tpot_p50_ms": out.get("tpot_p50_ms"),
            "tpot_p90_ms": out.get("tpot_p90_ms"),
            "met_both_limits": inside(pairs, out["sent"], ttft_ms, tpot_ms),
            "backlog": backlog, "lateness": out["lateness"],
            "programs_lowered": lowered,
            "drain_s": time.perf_counter() - t_end,
        })
    service.close()
    lowest, base, _ = min(tails, key=lambda t: t[0])
    lim_ttft = 2.0 * base["ttft_p90_ms"]
    lim_tpot = 2.0 * base["tpot_p90_ms"]
    log("sweep.against_twice_the_lowest_rate", {
        "lowest_rate_per_s": lowest,
        "latency_limits_ms": {"ttft": lim_ttft, "tpot": lim_tpot},
        "met_both_limits": {
            str(rate): inside(pairs, out["sent"], lim_ttft, lim_tpot)
            for rate, out, pairs in tails
        },
    })


if __name__ == "__main__":
    main()
