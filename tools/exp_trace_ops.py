"""A traced benchmark run that also keeps what the capture calls its
ops: ``python tools/exp_trace_ops.py --workload <cell> --seed <n>
[--seconds 50]`` is ``python3 -m benchmark.run ... --trace 1`` with one
more output, ``chiprun_out/ops_<cell>.json``: every distinct op text of
the traced slice (the first 400 characters) with its count and its
seconds, longest first, and the programs' totals.  For writing a reader
that has to find XLA's own ops (the v5e's captures carry no scope): read
the texts off a real capture first.  Needs the chip and
``PYTHONPATH=/root/repo``."""

from __future__ import annotations

import json
import sys
from pathlib import Path


def main() -> int:
    from benchmark import harness, run

    argv = sys.argv[1:]
    cell = argv[argv.index("--workload") + 1]
    result_line = harness.result_line

    def keeping_ops(*args, **kw):
        ctx = kw.get("layer_ctx")
        tr = ctx and ctx.get("trace")
        if tr is not None and tr.devices:
            tot = {}
            for spans in tr.ops.values():
                for name, s, e in spans:
                    t = tot.setdefault(name[:400], [0, 0.0])
                    t[0] += 1
                    t[1] += (e - s) / 1e9
            out = Path("chiprun_out")
            out.mkdir(exist_ok=True)
            with open(out / f"ops_{cell}.json", "w") as f:
                json.dump({
                    "busy_s": tr.busy_s(), "programs": tr.module_totals(),
                    "ops": sorted(([n, c, t] for n, (c, t) in tot.items()),
                                  key=lambda r: -r[2])[:600],
                }, f, indent=0)
        return result_line(*args, **kw)

    harness.result_line = keeping_ops
    if "--seconds" not in argv:
        argv += ["--seconds", "50"]
    return run.main(argv + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
