"""What the rematerialised train step holds, compiled for a DESCRIBED
v5e through the flash path (no chip: nothing runs, so no time is read).

    JAX_PLATFORMS=cpu PYTHONPATH=/root/repo python tools/exp_remat_fit.py \
        --layers 2,24 [--keep none | flash_out,flash_lse | ...]

``benchmark/aot_fit.py train``'s own lowering, with one thing more:
``ops/attention.py`` bound ``on_tpu`` by name when ``mlcomp_tpu.ops``
was imported, so the flag ``aot_fit`` sets on ``ops.pallas`` never
reaches the dispatch and its step carries XLA's reference attention
(no ``tpu_custom_call``).  This tool sets the dispatch's own name too,
and after each ``memory_analysis()`` line prints how many calls of each
flash kernel the compiled program holds: a layer rematerialised without
the kernel's residuals calls the forward kernel twice.

``--keep`` is the list of names the layer's policy keeps, by default
the one that ships (``flash_attention.REMAT_SAVED_NAMES``); ``none``
reads what a plain ``remat`` costs.
"""

import argparse
import json
import re

# the backward is ``flash_dq_dkv_kernel*`` where a KV head's float32 dq
# fits VMEM (``flash_attention.DQ_RESIDENT_BUDGET``), else the dq + dkv pair
KERNELS = ("flash_fwd", "flash_dq_dkv", "flash_dq_kernel", "flash_dkv")


def kernel_calls(text: str) -> dict:
    """Custom calls of each flash kernel in a compiled program's text."""
    heads = re.findall(r"^\s*(?:ROOT )?%(\w+)[.\w]* = .*custom-call\(", text,
                       flags=re.M)
    return {k: sum(h.startswith(k) for h in heads) for k in KERNELS}


def main():
    import mlcomp_tpu.ops.attention as attention
    from benchmark import aot_fit, cells
    from mlcomp_tpu.ops.pallas import flash_attention

    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", default="2,24")
    ap.add_argument("--keep", default=None)
    args = ap.parse_args()
    if args.keep is not None:
        flash_attention.REMAT_SAVED_NAMES = tuple(
            n for n in args.keep.split(",") if n and n != "none"
        )
    print(json.dumps({"keep": list(flash_attention.REMAT_SAVED_NAMES)}),
          flush=True)
    attention.on_tpu = lambda: True

    report = aot_fit._report

    def report_with_calls(tag, compiled, seconds):
        report(tag, compiled, seconds)
        print(json.dumps({"program": tag,
                          "custom_calls": kernel_calls(compiled.as_text())}),
              flush=True)

    aot_fit._report = report_with_calls
    with open(cells.HERE / "configs" / "internlm2-1_8b-train.json") as f:
        cfg = json.load(f)
    chip = aot_fit._describe()
    for n in args.layers.split(","):
        aot_fit.fit_train(cfg, int(n), True, chip)


if __name__ == "__main__":
    main()
