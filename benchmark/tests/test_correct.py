"""``correct`` decides: the program agrees with the plain reference at
rehearsal width, the lower-precision control does not, and a run whose
timed path is broken underneath comes out not correct.

The limits of the rehearsal mixes were set from CPU readings at this
width (PR 24), as the real cells' limits were from chip readings: serving
max_logit_gap sound <= 0.043 / int4-weight control >= 1.1 / int4-KV
control >= 0.53, mean_abs_logprob_err <= 0.017 / >= 0.48 / >= 0.16;
training grad_stat_gap sound <= 0.0025 / float8 control >= 0.0092.

Further down: a configuration's ``service`` and ``trainer`` mappings
reach the program whole (a paged KV layout, a mesh), and a key nobody
reads is refused instead of dropped."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import cells

SERVE = ("--workload", "chat-steady", "--seconds", "3", "--trace", "0")
TRAIN = ("--workload", "train-4k", "--seconds", "1", "--trace", "0")


def _limits(cell):
    return cells.Cell(cell, rehearsal=True).traffic["limits"]


@pytest.mark.parametrize("seed", [11, 2**31 + 12])
def test_serving_agrees_and_its_int4_control_does_not(rehearse, seed):
    seen, res = rehearse(*SERVE, "--seed", str(seed), "--control", "1")
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["platform"] == "cpu" and res["metrics"] == {}
    assert seen["programs_lowered_in_window"] == 0
    lim = _limits("chat-steady")
    for control in ("control", "control_kv"):  # int4 weights, int4 KV
        failed = [k for k in lim if seen[f"{control}.{k}"] > lim[k]]
        assert failed, f"{control} has to fail one of the cell's numbers"
        assert seen[f"{control}.mean_abs_logprob_err"] > 3 * (
            seen["correct.mean_abs_logprob_err"]["value"]
        )


def test_closed_loop_cell_runs_and_agrees(rehearse):
    seen, res = rehearse("--workload", "batch-offline", "--seconds", "3",
                         "--trace", "0", "--seed", "13")
    assert res["correct"] is True and res["attempted"] > 8
    assert "serve_tokens_per_s" in res["rehearsal_metrics"]
    # every number compared, beside its limit, is the line's last key
    assert list(res)[-1] == "compared"
    lim = _limits("batch-offline")
    assert {k: v["limit"] for k, v in res["compared"].items()} == {
        **lim, "programs_lowered_in_window": 0, "nothing_to_compare": 0}
    assert res["compared"]["max_logit_gap"]["value"] == (
        seen["correct.max_logit_gap"]["value"])


@pytest.mark.parametrize("seed", [14, 15])
def test_training_agrees_and_its_float8_control_does_not(rehearse, seed):
    seen, res = rehearse(*TRAIN, "--seed", str(seed), "--control", "1")
    assert res["correct"] is True
    lim = _limits("train-4k")
    failed = [k for k in lim if seen[f"control.{k}"] > lim[k]]
    assert failed, "the float8 control has to fail one of the cell's numbers"


def test_a_served_token_altered_where_it_is_produced_is_not_correct(
        rehearse, monkeypatch):
    from concurrent.futures import Future

    from mlcomp_tpu.serve import GenerationService

    real = GenerationService.submit

    def altered(self, *a, **kw):
        inner, outer = real(self, *a, **kw), Future()
        outer.rid = getattr(inner, "rid", 0)

        def relay(f):
            if f.exception() is not None:
                outer.set_exception(f.exception())
                return
            res = dict(f.result())
            res["ids"] = [(t + 1) % self.engine.vocab for t in res["ids"]]
            outer.set_result(res)

        inner.add_done_callback(relay)
        return outer

    monkeypatch.setattr(GenerationService, "submit", altered)
    seen, res = rehearse(*SERVE, "--seed", "16")
    assert res["correct"] is False
    assert seen["correct.max_logit_gap"]["ok"] is False


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        rehearse, monkeypatch):
    from mlcomp_tpu.train import loop

    real = loop.make_train_step

    def frozen(*a, **kw):
        step = real(*a, **kw)
        return lambda state, batch: (state, step(state, batch)[1])

    monkeypatch.setattr(loop, "make_train_step", frozen)
    seen, res = rehearse(*TRAIN, "--seed", "17")
    assert res["correct"] is False
    assert seen["correct.delta_norm_gap"]["ok"] is False
    assert seen["correct.loss_gap"]["ok"] is True  # first step still sound


def test_a_real_cell_on_the_cpu_fails_and_prints_no_result(capsys):
    from benchmark import run

    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "chat-steady", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert not capsys.readouterr().out.strip().endswith("}")


def test_the_service_mapping_reaches_the_engine_whole():
    from benchmark import serving
    from benchmark.harness import configure_jax

    cell = cells.Cell("chat-steady", rehearsal=True)
    cell.config["service"].update(kv_layout="paged", kv_page_tokens=16,
                                  engine_pipeline_depth=1)
    configure_jax(cell)
    service = serving.build_service(cell, 5, lambda *a: None)
    try:
        assert service.engine.kv_layout == "paged"
        assert service.engine.stats()["kv_layout"] == "paged"
    finally:
        service.close()


@pytest.mark.parametrize("key,error", [
    ("kv_layuot", TypeError),      # the service does not know it
    ("mesh", SystemExit),          # needs an object no data file holds
    ("seed", SystemExit),          # the benchmark's to set
])
def test_a_service_key_nobody_reads_is_refused(key, error):
    from benchmark import serving
    from benchmark.harness import configure_jax

    cell = cells.Cell("chat-steady", rehearsal=True)
    cell.config["service"][key] = "paged"
    configure_jax(cell)
    with pytest.raises(error):
        serving.build_service(cell, 5, lambda *a: None)


def test_the_trainer_mapping_reaches_the_trainer_whole():
    runner = cells._load_py(cells.HERE / "kinds" / "train_steps.py")
    cell = cells.Cell("train-4k", rehearsal=True)
    cell.config["trainer"].update(mesh={"fsdp": 2}, grad_accum=2)
    cfg = runner.trainer_config(cell, "rows.npz")
    assert cfg["mesh"] == {"fsdp": 2} and cfg["grad_accum"] == 2
    assert cfg["optimizer"]["name"] == "adafactor" and cfg["seed"] == 0
    assert cfg["data"]["train"]["batch_size"] == 2
    assert "seq_len" not in cfg and "steps_per_epoch" not in cfg
    cell.config["trainer"]["mseh"] = {"fsdp": 2}
    with pytest.raises(SystemExit, match="mseh"):
        runner.trainer_config(cell, "rows.npz")


MESH_RUN = """
import json, sys, time
from benchmark import cells, device
from benchmark.harness import configure_jax
cell = cells.Cell("train-4k", rehearsal=True)
cell.config["trainer"]["mesh"] = {"fsdp": 2}
cell.chips = 2
configure_jax(cell)
res = cells.kind_runner("train_steps")(
    cell=cell, seed=31, seconds=0.5, trace=False, control=False,
    dev=device.describe(2, True), t_start=time.perf_counter())
print(json.dumps(res))
"""


def test_training_on_the_mesh_a_configuration_names_agrees():
    """Two virtual CPU devices, parameters sharded over ``fsdp``: the
    seeded weights land where the trainer keeps them and the first epoch
    still agrees with the one-device reference."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(cells.ROOT),
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run([sys.executable, "-c", MESH_RUN], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.splitlines()
    mesh = next(json.loads(ln.split(" ", 1)[1]) for ln in lines
                if ln.startswith("setup.mesh "))
    assert mesh["fsdp"] == 2
    res = json.loads(lines[-1])
    assert res["correct"] is True and res["device"]["count"] == 2


# An architecture whose layers are not all alike (tests/two_kinds.py):
# moe_lm at rehearsal width, a dense layer then a routed one, served in
# float32 on weights stated as bfloat16.  Registered here, never a cell.
TWO_KINDS = {
    "reference": "two_kinds",
    "hidden_size": 256, "num_hidden_layers": 2, "num_attention_heads": 2,
    "num_key_value_heads": 1, "intermediate_size": 256, "vocab_size": 512,
    "n_experts": 4, "experts_per_token": 2, "moe_every": 2,
    "as_run": {"rope_base": 10000.0, "norm_eps": 1e-06},
    "rounding": {"weights": "bfloat16"},
    "model": {"name": "moe_lm", "vocab_size": 512, "hidden": 256,
              "layers": 2, "heads": 2, "kv_heads": 1, "n_experts": 4,
              "d_ff": 256, "k": 2, "moe_every": 2, "dtype": "float32"},
    "service": {"batch_sizes": [4], "prompt_buckets": [32, 64],
                "max_new_buckets": [16], "prefill_chunk": 32,
                "steps_per_dispatch": 4, "request_timeout_s": 600.0},
}
# CPU readings at this width (PR 27, seeds 41-43 and 2**31 + 44): sound
# max_logit_gap 0.0 on all four / the int8 control 0.011-0.193 / every
# layer taken for the first kind 2.19-3.50; mean_abs_logprob_err sound
# 2e-6 - 3e-6 / control 0.021-0.039 / first kind everywhere 0.69-0.92
TWO_KINDS_LIMITS = {"max_logit_gap": 0.003, "mean_abs_logprob_err": 0.0003}


@pytest.fixture(scope="module")
def two_kinds_served():
    """One window of the fixture through ``GenerationService`` on the
    CPU: (configuration, seed, sampled finished requests, pad length)."""
    import contextlib

    from benchmark import serving
    from benchmark.harness import configure_jax

    arch = cells._load_py(cells.HERE / "tests" / "two_kinds.py")
    cells._ARCHITECTURES["two_kinds"] = arch
    cell = cells.Cell("chat-steady", rehearsal=True)
    cell.config = cfg = TWO_KINDS
    configure_jax(cell)
    seed = 41
    service = serving.build_service(cell, seed, lambda *a: None)
    try:
        assert set(service.variables["params"]) >= {
            "DecoderLayer_0", "MoELayer_0"}
        win = serving.open_loop(service, cell, seed, 2.0, cfg["vocab_size"],
                                lambda name: contextlib.nullcontext())
        serving.drain(win["reqs"], 120.0)
        samples = serving.sample_finished(win["reqs"], 6, seed)
    finally:
        service.close()
    assert len(samples) == 6
    yield cfg, seed, samples, 64 + 16
    del cells._ARCHITECTURES["two_kinds"]


def test_a_model_with_two_kinds_of_layer_agrees_with_its_reference(
        two_kinds_served):
    from benchmark.harness import judge
    from benchmark.reference.check_serve import serve_readings

    arch = cells.architecture(TWO_KINDS)
    assert arch.layer_kinds(arch.dims_of(TWO_KINDS)) == ["dense", "moe"]
    readings = serve_readings(*two_kinds_served)
    assert readings["tokens_compared"] >= 48
    assert judge(readings, TWO_KINDS_LIMITS) is True


def test_the_second_kind_computed_as_the_first_is_not_correct(
        two_kinds_served, monkeypatch):
    """A comparison that took every layer for the first kind: the routed
    layer's place is computed by the dense layer's function."""
    from benchmark.harness import judge
    from benchmark.reference.check_serve import serve_readings

    arch = cells.architecture(TWO_KINDS)
    monkeypatch.setattr(arch, "layer_kinds",
                        lambda d: ["dense"] * d["layers"])
    readings = serve_readings(*two_kinds_served)
    assert judge(readings, TWO_KINDS_LIMITS) is False


def test_a_configuration_stated_in_bfloat16_has_an_int8_control_that_fails(
        two_kinds_served):
    from benchmark.reference.check_serve import passes_of, serve_readings

    assert [p[:2] for p in passes_of(TWO_KINDS, True)] == [
        ("", None), ("control", 127)]           # no statement on kv: no control
    assert [p[:2] for p in passes_of(
        cells.Cell("chat-steady", rehearsal=True).config, True)] == [
        ("", 127), ("control", 7), ("control_kv", 127)]
    readings = serve_readings(*two_kinds_served, control=True)
    lim = TWO_KINDS_LIMITS
    assert all(readings[k] <= lim[k] for k in lim)
    failed = [k for k in lim if readings[f"control.{k}"] > lim[k]]
    assert failed, "the int8 control has to fail one of the numbers"
    assert "control_kv.max_logit_gap" not in readings


def test_the_training_comparison_refuses_layers_of_several_kinds(
        two_kinds_served):
    from benchmark.reference.check_train import one_kind

    arch = cells.architecture(TWO_KINDS)
    with pytest.raises(SystemExit, match="one kind of layer"):
        one_kind(arch, arch.dims_of(TWO_KINDS))


def test_a_configuration_names_its_architecture():
    cell = cells.Cell("train-4k", rehearsal=True)
    arch = cells.architecture(cell.config)
    assert arch is cells.architecture(cell.config)  # loaded once
    for name in ("dims_of", "layer_kinds", "layer_weights", "top_weights",
                 "program_layer", "program_top", "layer_key", "layer",
                 "embed", "logits", "rows_per_block", "LAYER_LEAVES",
                 "TOP_LEAVES", "CONTRACT_AXES"):
        assert hasattr(arch, name), name
    with pytest.raises(SystemExit):
        cells.architecture({"reference": "no_such_architecture"})
    with pytest.raises(SystemExit):
        cells.architecture({})
