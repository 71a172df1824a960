"""Optimizer steps back to back: the ``Trainer`` of ``cli dag``, built
from a configuration mapping as ``executors/train.py`` builds it.

Set-up makes ONE trainer — the compiled step with its state — drives it
from the seed through its first epoch (which the plain reference has
followed beforehand, in float32, before the trainer's state existed),
warms it, and hands that same object to the window.  The window calls
what set-up called: ``trainer.train_epoch()``, whose loader feeds rows
the benchmark made from the seed (an ``npz`` data set in the run's
temporary directory), every row different, in file order.
"""

from __future__ import annotations

import gc
import os
import shutil
import tempfile
import time
from typing import Any, Dict

import numpy as np

from benchmark import cells, stats, traffic
from benchmark import weights as W


# what the benchmark itself reads from a configuration's ``trainer``
# mapping; every other key goes to the ``Trainer`` as it stands
BENCHMARK_KEYS = ("batch_size", "seq_len", "steps_per_epoch")
# what the benchmark sets and a configuration therefore may not
SET_HERE = ("model", "seed", "epochs", "data")


def trainer_config(cell, data_path: str) -> Dict[str, Any]:
    """The mapping ``Trainer`` is built from, as ``executors/train.py``
    hands it one: the configuration's ``trainer`` mapping WHOLE
    (optimizer, loss, metrics, ``mesh``, ``grad_accum``, ...), plus the
    model, the data and what the benchmark fixes.  A key the Trainer's
    constructor never reads is refused: it would run one thing under
    another's name."""
    import inspect
    import re

    from mlcomp_tpu.train.loop import Trainer

    tr = dict(cell.config["trainer"])
    batch = int(tr["batch_size"])
    for k in BENCHMARK_KEYS:
        tr.pop(k)
    taken = sorted(set(tr) & set(SET_HERE))
    if taken:
        raise SystemExit(f"the benchmark sets {taken}; not the trainer mapping")
    reads = set(re.findall(r'cfg(?:\.get\(|\[)"(\w+)"',
                           inspect.getsource(Trainer.__init__)))
    unknown = sorted(set(tr) - reads)
    if unknown:
        raise SystemExit(
            f"trainer keys the Trainer never reads: {unknown}; it reads "
            f"{sorted(reads)}"
        )
    return {
        **tr,
        "model": dict(cell.config["model"]),
        # the trainer's own seed only feeds its init (replaced by the
        # benchmark's weights) and dropout (none), and it is baked into
        # the compiled step as a constant: a seed that moved would miss
        # the compile cache in every run
        "seed": 0,
        "epochs": 1 << 30,
        "data": {"train": {
            "name": "npz", "path": data_path,
            "batch_size": batch, "shuffle": False,
        }},
    }


def _canonical(made, tree, leaves) -> list:
    """``tree`` is in the program's layout; its leaves in the
    architecture's canonical order (``made`` is ``M.program_layer`` or
    ``M.program_top``, which say where each canonical leaf sits)."""
    import jax

    where = jax.tree.leaves(made({n: n for n in leaves}))
    by_name = dict(zip(where, jax.tree.leaves(tree)))
    return [float(by_name[n]) for n in leaves]


def program_readings(trainer, cell, seed: int) -> Dict[str, Any]:
    """The optimizer's second-moment statistic and each leaf's change,
    from the trainer's state after its first epoch; initial weights are
    regenerated from the seed, a layer at a time."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference.check_train import (
        _fac_state, leaf_stat, one_kind,
    )

    M = cells.architecture(cell.config)
    d = M.dims_of(cell.config)
    kind = one_kind(M, d)
    key = W.seed_key(seed)
    params = trainer.state.params
    fac = _fac_state(trainer.state.opt_state)

    def numbers(w0, p, v_row, v_col, v):
        dn = jax.tree.map(lambda a, b: jnp.sqrt(jnp.sum((a - b) ** 2)), p, w0)
        gs = jax.tree.map(lambda r, c, f: leaf_stat(r, c, f, 0),
                          v_row, v_col, v)
        return dn, gs

    @jax.jit
    def of_layer(key, i, p, v_row, v_col, v):
        w0 = M.program_layer(
            M.layer_weights(key, i, d, jnp.float32, kind), kind)
        return numbers(w0, p, v_row, v_col, v)

    @jax.jit
    def of_top(key, p, v_row, v_col, v):
        w0 = M.program_top(M.top_weights(key, d, jnp.float32))
        return numbers(w0, p, v_row, v_col, v)

    dnorm, gstat = [], []
    of_kind = lambda w: M.program_layer(w, kind)  # noqa: E731
    for i in range(d["layers"]):
        n = M.layer_key(i, d)
        dn, gs = of_layer(key, jnp.int32(i), params[n], fac.v_row[n],
                          fac.v_col[n], fac.v[n])
        dnorm += _canonical(of_kind, dn, M.LAYER_LEAVES)
        gstat += _canonical(of_kind, gs, M.LAYER_LEAVES)
    top = list(M.program_top({n: n for n in M.TOP_LEAVES}))
    sub = lambda t: {k: t[k] for k in top}  # noqa: E731
    dn, gs = of_top(key, sub(params), sub(fac.v_row), sub(fac.v_col),
                    sub(fac.v))
    dnorm += _canonical(M.program_top, dn, M.TOP_LEAVES)
    gstat += _canonical(M.program_top, gs, M.TOP_LEAVES)
    return {"grad_stat": gstat, "delta_norm": dnorm}


def compare(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    return {
        "loss_gap": abs(prog["loss"] - ref["loss"]) / abs(ref["loss"]),
        "grad_stat_gap": stats.worst_leaf_gap(prog["grad_stat"],
                                              ref["grad_stat"]),
        "delta_norm_gap": stats.worst_leaf_gap(prog["delta_norm"],
                                               ref["delta_norm"]),
    }


def run(cell, seed: int, seconds: float, trace: bool, control: bool,
        dev: Dict[str, Any], t_start: float) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from benchmark import device as D
    from benchmark import harness as H
    from benchmark.reference import quant
    from benchmark.reference.check_train import train_reference

    log = H.log
    cfg, tr, mix = cell.config, cell.config["trainer"], cell.traffic
    d = cells.architecture(cfg).dims_of(cfg)
    b, s, e = int(tr["batch_size"]), int(tr["seq_len"]), int(
        tr["steps_per_epoch"])
    rows = traffic.token_rows(b * e, s, d["vocab"], seed)
    counter = H.LowerCounter()

    # the reference first, while the device is empty; its time is not set-up
    t_ref = time.perf_counter()
    ref = train_reference(cfg, seed, rows, e)
    # the control: every product's operands at the nearest precision
    # below the one the configuration states (bfloat16 -> float8)
    ctl = train_reference(
        cfg, seed, rows, e,
        round_fn=quant.operand_round(
            quant.below("operands", quant.stated(cfg, "operands"))),
    ) if control else None
    gc.collect()
    reference_s = time.perf_counter() - t_ref
    log("reference_s", reference_s)
    log("reference.loss", ref["loss"])

    work = tempfile.mkdtemp(prefix="bench_train_")
    try:
        from mlcomp_tpu.train.loop import Trainer

        path = os.path.join(work, "rows.npz")
        np.savez(path, x=rows)
        t0 = time.perf_counter()
        trainer = Trainer(trainer_config(cell, path))
        log("setup.build_trainer_s", time.perf_counter() - t0)
        log("setup.mesh", {k: int(v) for k, v in trainer.mesh.shape.items()})
        t0 = time.perf_counter()
        # like a restored checkpoint: the benchmark's seeded weights take
        # the place of the trainer's own init, which is freed first (two
        # float32 copies of the full model do not fit beside each other)
        state, trainer.state = trainer.state, None
        abstract = jax.eval_shape(lambda: state.params)
        # each leaf goes where the trainer keeps it: on a mesh (the
        # configuration's ``trainer.mesh``) no chip holds the whole
        where = jax.tree.map(lambda x: x.sharding, state.params)
        for leaf in jax.tree.leaves(state.params):
            leaf.delete()
        t1 = time.perf_counter()
        params = W.program_params(
            cells.architecture(cfg), seed, d, jnp.float32, shardings=where)
        jax.block_until_ready(params)
        log("setup.program_params_s", time.perf_counter() - t1)
        W.check_layout(params, abstract)
        trainer.state = state.replace(params=params)
        del params, state
        gc.collect()
        log("setup.init_weights_s", time.perf_counter() - t0)
        log("memory.after_build", D.memory(cell.chips))
        t0 = time.perf_counter()
        first = trainer.train_epoch()
        log("setup.first_epoch_s", time.perf_counter() - t0)
        log("loss.epoch_0", first["loss"])
        prog = {"loss": float(first["loss"]),
                **program_readings(trainer, cell, seed)}
        for k in range(int(mix["warm_epochs"])):
            t0 = time.perf_counter()
            out = trainer.train_epoch()
            log(f"setup.warm_epoch_{k}_s", time.perf_counter() - t0)
            log(f"loss.epoch_{k + 1}", out["loss"])
        mem_open = D.memory(cell.chips)
        log("memory.after_warm", mem_open)
        sl = H.TracedSlice.steady(trace, seconds, mix)
        watch = H.GcWatch()
        watch.settle()
        setup_s = time.perf_counter() - t_start - reference_s
        log("setup.compile", counter.totals)
        log("setup_s", setup_s)
        epochs, losses, ends = 0, [], []
        with counter.window(), watch.window():
            t0 = time.perf_counter()
            sl.start(t0)
            while True:
                with sl.annotate("bench.train_epoch"):
                    out = trainer.train_epoch()
                epochs += 1
                losses.append(out["loss"])
                now = time.perf_counter()
                ends.append(now)
                if now - t0 >= seconds:
                    break
            window_s = now - t0
            lowered = counter.n
        took = np.diff([t0] + ends)
        log("epoch_s", {"min": float(took.min()),
                        "median": float(np.median(took)),
                        "max": float(took.max()),
                        "slowest_at": int(took.argmax())})
        log("programs_lowered_in_window", lowered)
        log("window_s", window_s)
        log("steps", epochs * e)
        log("loss.window", losses)
        tokens_per_s = epochs * e * b * s / window_s
        log("train_tokens_per_s", tokens_per_s)
        H.mark("window_closed")
        trc = sl.load()
        H.mark("trace_read")
        mem = D.memory(cell.chips)
        log("memory.after_window", mem)
        finite = all(np.isfinite(x) for x in losses)
        del trainer
        gc.collect()
    finally:
        shutil.rmtree(work, ignore_errors=True)
    readings = compare(prog, ref)
    ok = H.judge(readings, mix["limits"])
    ok = H.compare("programs_lowered_in_window", lowered, 0) and ok
    ok = H.compare("losses_not_finite", int(not finite), 0) and ok
    if ctl is not None:
        for k, v in compare(ctl, ref).items():
            log(f"control.{k}", v)
    e2e = {"train_tokens_per_s": tokens_per_s, "setup_s": setup_s}
    ctx = {
        "trace": trc, "slice_s": sl.length_s, "slice": (sl.t_lo, sl.t_hi),
        "cell": cell, "peaks": dev["peaks"], "e2e": e2e,
        "step_ms": window_s * 1e3 / (epochs * e),
        "tokens_per_step": b * s,
    } if trace else None
    return H.result_line(cell, dev, trace, ok, attempted=epochs * e,
                         failed=0, e2e=e2e, layer_ctx=ctx,
                         memory_peak=mem["peak"],
                         memory_steady=max(mem_open["in_use"], mem["in_use"]))
