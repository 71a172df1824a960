"""Run one benchmark cell and print, beside its result line, the
engine's ``stats()["attention"]`` block at every ``stats()`` call the
harness makes (one before the timed window, one after the drain), so
``rows_attended_share`` of the window is the difference of the two, and
``kv_rows_written`` over ``rows_total`` x K is the share of the slot
rows a step's kernels wrote a token into; ``kv_tokens_fetched`` is what
the decode kernel's walk moved for the ``kv_tokens_attended`` it read
(``kv_fetch_live_share``: attended over fetched, of the whole run up to
the call; difference the two calls' counts for the window's).  A second
line carries the
``pipeline`` block and ``prefills``: ``inserts_behind_dispatch`` over
``prefills`` is the share of admissions whose insert found a dispatch
in flight, ``occupancy`` the mean in-flight depth after an issue:

    python tools/bench_attention_rows.py --workload chat-steady \\
        --seed 7 --seconds 50 --trace 0

Arguments are ``benchmark.run``'s; nothing under ``benchmark/`` is
touched and the run makes no call it would not make anyway."""
import json
import runpy
import sys

from mlcomp_tpu.serve import GenerationService

_stats = GenerationService.stats


def stats(self):
    out = _stats(self)
    eng = out["engine"]
    print("attention_rows " + json.dumps(eng["attention"]),
          file=sys.stderr, flush=True)
    print("pipeline " + json.dumps(
        dict(eng["pipeline"], prefills=eng["prefills"])
    ), file=sys.stderr, flush=True)
    return out


GenerationService.stats = stats
runpy.run_module("benchmark.run", run_name="__main__")
