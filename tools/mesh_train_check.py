"""SPMD-vs-one-device check of a train stage, in ONE process on a
multi-chip host (``chip_smoke.py --chips 4`` runs it as a child).

Given a DAG YAML whose ``train`` task carries a ``mesh:``, this

1. builds the task's ``Trainer`` over a one-device mesh
   (``make_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])``) and runs
   the task's epochs (one optimizer step each in the smoke configs),
   returning the per-epoch losses — what the SPMD run's losses are
   compared with;
2. builds the same ``Trainer`` over the task's mesh and checks the
   layout: every parameter has addressable shards on every device of
   the mesh, and the attention q/k/v kernels carry ``tp`` in their spec.

Prints one JSON object.

    python -m tools.mesh_train_check configs/lm_1p2b_mesh.yml
"""

from __future__ import annotations

import gc
import json
import re
import sys


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    import jax

    from mlcomp_tpu.dag import parse_dag
    from mlcomp_tpu.parallel.mesh import MeshSpec, make_mesh
    from mlcomp_tpu.train.loop import Trainer
    from mlcomp_tpu.utils.chips import device_summary

    task = next(t for t in parse_dag(argv[0]).tasks if t.executor == "train")
    cfg = dict(task.args)
    if not cfg.get("mesh"):
        raise SystemExit(f"{argv[0]}: the train task has no mesh to check")
    out = {"device": device_summary(), "mesh": dict(cfg["mesh"])}

    # the one-device run first: its 1.2B step alone fills a chip, so it
    # must not find another trainer's shards there
    one = Trainer(
        {k: v for k, v in cfg.items() if k != "mesh"},
        mesh=make_mesh(MeshSpec(dp=1), devices=jax.devices()[:1]),
    )
    out["one_device_losses"] = [
        one.train_epoch()["loss"] for _ in range(one.epochs)
    ]
    del one
    gc.collect()

    trainer = Trainer(cfg)
    n_dev = trainer.mesh.devices.size
    flat = jax.tree_util.tree_flatten_with_path(trainer.state.params)[0]
    names = {jax.tree_util.keystr(path): leaf for path, leaf in flat}
    out["params"] = len(names)
    out["params_not_on_every_device"] = sorted(
        name for name, leaf in names.items()
        if len({s.device for s in leaf.addressable_shards}) != n_dev
    )
    qkv = {
        name: str(leaf.sharding.spec) for name, leaf in names.items()
        if re.search(r"\['(q|k|v)'\]\['kernel'\]$", name)
    }
    out["qkv_kernels"] = len(qkv)
    out["qkv_kernels_without_tp"] = sorted(
        name for name, spec in qkv.items() if "tp" not in spec
    )
    out["qkv_spec"] = next(iter(qkv.values()), None)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
