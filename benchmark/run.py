"""The benchmark's one command.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: loads, warms exactly the cell's shapes, measures for
``--seconds``, checks the timed path's outputs against the plain
reference, prints the contract's JSON object as the last line of its
standard output, exits 0.  ``--rehearsal 1`` swaps in the tiny CPU
configuration of the same cell; ``--control 1`` adds the lower-precision
control's readings to the lines before the result (the outputs check).
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import cells

    cell = cells.Cell(args.workload, rehearsal=bool(args.rehearsal))
    from benchmark import device as D
    from benchmark.harness import configure_jax, log

    cache_dir = configure_jax(cell)

    dev = D.describe(cell.chips, cell.rehearsal)
    log("device", {k: dev[k] for k in ("platform", "kind", "count")})
    log("compile_cache_dir", cache_dir)
    run = cells.kind_runner(cell.traffic["kind"])
    result = run(cell=cell, seed=args.seed, seconds=args.seconds,
                 trace=bool(args.trace), control=bool(args.control),
                 dev=dev, t_start=_T_START)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    for name, c in result["compared"].items():
        print(f"correct.{name} value={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
