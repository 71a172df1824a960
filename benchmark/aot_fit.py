"""Ahead-of-time fit: compile a cell's programs for a DESCRIBED TPU
v5e (``jax.experimental.topologies``, nothing attached, nothing runs)
and print ``memory_analysis()`` — the evidence for the training depth
and batch, taken in the sandbox before any chip call.

    JAX_PLATFORMS=cpu python3 -m benchmark.aot_fit train --layers 12,16,20
    JAX_PLATFORMS=cpu python3 -m benchmark.aot_fit reference --layers 16

``train`` compiles the Trainer's own step (``train/loop.py
make_train_step`` over the configuration's model, loss and optimizer) at
the cell's batch and sequence length; ``reference`` compiles the plain
float32 reference's heaviest program, which has to fit the same chip before the
trainer's state exists.  The code under test asks ``jax.default_backend()``
which kernels to use and would see the CPU here, so this script — and
nothing in the program — tells it that it is compiling for a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _describe():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return SingleDeviceSharding(topo.devices[0])


def _report(tag: str, compiled, seconds: float) -> None:
    m = compiled.memory_analysis()
    gib = 1 << 30
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes)
    print(json.dumps({
        "program": tag, "compile_s": round(seconds, 1),
        "arguments_gib": round(m.argument_size_in_bytes / gib, 2),
        "outputs_gib": round(m.output_size_in_bytes / gib, 2),
        "aliased_gib": round(m.alias_size_in_bytes / gib, 2),
        "temporaries_gib": round(m.temp_size_in_bytes / gib, 2),
        "total_gib": round(total / gib, 2),
    }), flush=True)


def fit_train(cfg, layers: int, remat: bool, chip) -> None:
    import jax
    import jax.numpy as jnp

    import mlcomp_tpu.ops.pallas as pallas

    pallas.on_tpu = lambda: True
    pallas.interpret_default = lambda: False
    from mlcomp_tpu.models import create_model
    from mlcomp_tpu.train.loop import make_train_step
    from mlcomp_tpu.train.losses import create_loss
    from mlcomp_tpu.train.optim import create_optimizer
    from mlcomp_tpu.train.state import TrainState, init_model

    tr = cfg["trainer"]
    model = create_model({**cfg["model"], "layers": layers, "remat": remat})
    tx = create_optimizer(dict(tr["optimizer"]))
    b, s = int(tr["batch_size"]), int(tr["seq_len"])

    def make_state():
        params, mstate = init_model(
            model, {"x": jnp.zeros((1, 8), jnp.int32)}, jax.random.PRNGKey(0)
        )
        return TrainState.create(model.apply, params, tx, mstate)

    on = lambda t: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), t
    )
    state = on(jax.eval_shape(make_state))
    batch = {"x": jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=chip)}
    step = jax.jit(
        make_train_step(create_loss(tr["loss"]), {},
                        rng_key=jax.random.PRNGKey(1)),
        donate_argnums=(0,),
    )
    t0 = time.perf_counter()
    try:
        compiled = step.lower(state, batch).compile()
    except Exception as e:  # what the chip's compiler would refuse
        print(json.dumps({"program": f"train layers={layers} remat={remat}",
                          "refused": str(e)[:400]}), flush=True)
        return
    _report(f"train layers={layers} remat={remat} B={b} S={s}", compiled,
            time.perf_counter() - t0)


def fit_reference(cfg, layers: int, chip) -> None:
    """The reference's heaviest program: one layer's backward pass and
    optimizer update, float32 at ``highest``.  Beside it live the
    reference's parameters and one saved activation per layer."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference.check_train import Reference

    tr = cfg["trainer"]
    b, s = int(tr["batch_size"]), int(tr["seq_len"])
    ref = Reference({**cfg, "num_hidden_layers": layers}, 0)
    on = lambda t: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), t
    )
    w = jax.eval_shape(ref.init_layer, ref.key, jnp.int32(0))
    st = jax.eval_shape(ref.init_opt, w)
    x = jax.ShapeDtypeStruct((b, s, ref.d["hidden"]), jnp.float32,
                             sharding=chip)
    t0 = time.perf_counter()
    try:
        compiled = ref.bwd.lower(on(w), on(st), x, x).compile()
    except Exception as e:
        print(json.dumps({"program": "reference layer backward",
                          "refused": str(e)[:400]}), flush=True)
        return
    _report(f"reference layer backward+update B={b} S={s}", compiled,
            time.perf_counter() - t0)
    n_layer = sum(v.size for v in jax.tree.leaves(w))
    top = 2 * ref.d["vocab"] * ref.d["hidden"]
    gib = 1 << 30
    print(json.dumps({
        "reference_resident_gib": {
            "parameters_f32": round((n_layer * layers + top) * 4 / gib, 2),
            "saved_activations": round(layers * b * s * ref.d["hidden"] * 4
                                       / gib, 2),
        }}), flush=True)


def main() -> None:
    from benchmark import cells

    ap = argparse.ArgumentParser(prog="benchmark.aot_fit")
    ap.add_argument("what", choices=("train", "reference"))
    ap.add_argument("--config", default="internlm2-1_8b-train")
    ap.add_argument("--layers", default="")
    ap.add_argument("--remat", type=int, default=0)
    args = ap.parse_args()
    with open(cells.HERE / "configs" / f"{args.config}.json") as f:
        cfg = json.load(f)
    chip = _describe()
    layers = [int(x) for x in args.layers.split(",") if x] or [
        int(cfg["num_hidden_layers"])
    ]
    for n in layers:
        if args.what == "train":
            fit_train(cfg, n, bool(args.remat), chip)
        else:
            fit_reference(cfg, n, chip)


if __name__ == "__main__":
    main()
