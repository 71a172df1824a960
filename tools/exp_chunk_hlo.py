"""Read a serve cell's chunk programs as the backend compiled them:
does a prefill chunk multiply the head for every position, or for the
one row it keeps?  Builds the cell's service as the benchmark does,
lowers and compiles ``_fused_dispatch_fn(c, k)`` and
``_prefill_chunk_fn(c)`` at the cell's chunk width and K (nothing
runs), writes each program's text under ``chiprun_out/chunk_hlo/`` and
prints one JSON line a program: the temporaries of
``memory_analysis()``, every distinct tensor shape that carries the
vocabulary axis, and the instructions that make a whole chunk's logits,
(…, chunk, vocab), if any does.

    python tools/exp_chunk_hlo.py --workload mixed-length-offline

``--rehearsal 1`` reads the tiny CPU configuration (a rehearsal of the
script: there the vocabulary's 512 is an MLP's width too, so shapes
match that are no logits).  Nothing under ``benchmark/`` is touched.
"""
import argparse
import json
import re
import sys
from pathlib import Path

_SHAPE = re.compile(r"\b(?:pred|[a-z]+\d+)\[([\d,]+)\]")


def vocab_shapes(text: str, vocab: int) -> dict:
    """Distinct tensor shapes of a program's text that hold an axis of
    ``vocab`` entries, with how often each is written."""
    seen: dict = {}
    for m in _SHAPE.finditer(text):
        dims = tuple(int(d) for d in m.group(1).split(","))
        if vocab in dims:
            seen[dims] = seen.get(dims, 0) + 1
    return seen


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rehearsal", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/chunk_hlo")
    args = ap.parse_args()

    import jax

    from benchmark import cells, serving
    from benchmark import device as D
    from benchmark.harness import configure_jax

    cell = cells.Cell(args.workload, rehearsal=bool(args.rehearsal))
    configure_jax(cell)
    D.describe(cell.chips, cell.rehearsal)
    service = serving.build_service(cell, args.seed, lambda *a: None)
    eng = service.engine
    vocab = int(cell.config["vocab_size"])
    c = eng._chunk_width(eng.prompt_buckets[-1])
    k = eng.steps_per_dispatch
    i32 = jax.numpy.int32

    def spec(*shape, dtype=i32):
        return jax.ShapeDtypeStruct(shape, dtype)

    chunk = (spec(1, c), spec(1, c), spec(1, eng.l_buf, dtype=bool))
    adm = jax.eval_shape(eng._prefill_init_fn(), spec())
    programs = {
        "fused": (eng._fused_dispatch_fn(c, k),
                  (eng.variables, jax.eval_shape(eng._fresh_dstate), adm)
                  + chunk),
        "staged": (eng._prefill_chunk_fn(c), (eng.variables, adm) + chunk),
    }
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    for name, (fn, fn_args) in programs.items():
        compiled = fn.lower(*fn_args).compile()
        text = compiled.as_text()
        (out / f"{args.workload}.{name}.txt").write_text(text)
        shapes = vocab_shapes(text, vocab)
        whole = sorted(s for s in shapes if s[-2:] == (c, vocab))
        tags = ["[" + ",".join(map(str, s)) + "]" for s in whole]
        made = [
            line.strip()[:400] for line in text.splitlines()
            if " = " in line and any(
                t in line.split(" = ", 1)[1].split("(", 1)[0] for t in tags)
        ]
        m = compiled.memory_analysis()
        print(json.dumps({
            "workload": args.workload, "program": name, "chunk": c, "k": k,
            "platform": jax.devices()[0].platform,
            "temporaries_bytes": m.temp_size_in_bytes,
            "vocab_shapes": {str(list(s)): n for s, n in
                             sorted(shapes.items())},
            "whole_chunk_logits": [list(s) for s in whole],
            "made_by": made[:12],
        }), flush=True)
    service.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
