"""Bring-up contracts (PR 22): the pieces that let the program start on
a real chip and that no CPU path exercises by itself — where the compile
cache goes, how children are pinned to chips, that a TPU backend never
slides onto a reference path, that the native core says whether it
loaded."""

import os
import subprocess
import sys

import pytest

from mlcomp_tpu.utils import compile_cache
from mlcomp_tpu.utils.chips import chip_visibility_env, device_summary

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_respects_the_variable(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV, "/somewhere/else")
    assert compile_cache.place_compile_cache() == "/somewhere/else"
    assert os.environ[compile_cache.ENV] == "/somewhere/else"


def test_compile_cache_default_is_one_fixed_dir_in_the_checkout():
    """Unset, a fresh entry-point process lands on <checkout>/.jax_cache
    and exports it for its children — probed in a child so this test
    process's own jax config is never touched."""
    env = {k: v for k, v in os.environ.items() if k != compile_cache.ENV}
    env["PYTHONPATH"] = ROOT
    out = subprocess.run(
        [sys.executable, "-c",
         "import os; from mlcomp_tpu.utils.compile_cache import *; "
         "p = place_compile_cache(); import jax; "
         "print(p, os.environ[ENV], jax.config.jax_compilation_cache_dir)"],
        env=env, capture_output=True, text=True, check=True,
    ).stdout.split()
    assert out == [os.path.join(ROOT, ".jax_cache")] * 3


def test_chip_visibility_env():
    one = chip_visibility_env([2])
    assert one == {
        "TPU_VISIBLE_CHIPS": "2",
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }
    assert chip_visibility_env(range(2, 4)) == {"TPU_VISIBLE_CHIPS": "2,3"}


def test_device_summary_names_the_device():
    dev = device_summary()
    assert dev["platform"] == "cpu" and dev["count"] == 8
    assert set(dev) >= {"device_kind", "visible_chips", "peak_bytes_in_use"}


def test_fleet_launcher_pins_each_replica_to_its_own_chips(monkeypatch):
    from mlcomp_tpu.fleet import manager

    seen = []

    class FakePopen:
        pid = 1

        def __init__(self, argv, env=None, **kw):
            seen.append((argv[argv.index("--port") + 1], env))

    monkeypatch.setattr(subprocess, "Popen", FakePopen)
    pinned = manager.SubprocessLauncher(
        ["--model", "m.yml"], chips=1, port_base=9001
    )
    for port in (9001, 9003):
        pinned.spawn(f"r{port}", port)
    manager.SubprocessLauncher(["--model", "m.yml"]).spawn("r", 9001)
    assert [(p, e and e["TPU_VISIBLE_CHIPS"]) for p, e in seen] == [
        ("9001", "0"), ("9003", "2"), ("9001", None),
    ]


@pytest.mark.parametrize("backend,want", [
    ("tpu", False), ("cpu", True), ("gpu", RuntimeError),
])
def test_kernels_compile_on_tpu_interpret_on_cpu_nothing_else(
    monkeypatch, backend, want
):
    import jax

    from mlcomp_tpu.ops import pallas

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is RuntimeError:
        with pytest.raises(RuntimeError, match="unsupported JAX backend"):
            pallas.interpret_default()
    else:
        assert pallas.interpret_default() is want


def test_attention_on_tpu_raises_instead_of_the_reference(monkeypatch):
    """A kernel that cannot be built on a TPU backend is an error, not
    a warning and an O(S^2) reference path."""
    import jax.numpy as jnp

    from mlcomp_tpu.ops import attention
    from mlcomp_tpu.ops.pallas import flash_attention as fa

    def refuse(*a, **k):
        raise NotImplementedError("the compiler said no")

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(fa, "flash_attention", refuse)
    q = jnp.zeros((1, 128, 2, 64))
    with pytest.raises(NotImplementedError, match="the compiler said no"):
        attention.dot_product_attention(q, q, q, causal=True)
    # shapes the kernel is not built for stay the reference's by design
    short = jnp.zeros((1, 64, 2, 64))
    assert attention.dot_product_attention(
        short, short, short, causal=True
    ).shape == short.shape


def test_paged_engine_refuses_an_unservable_page_size_on_tpu(monkeypatch):
    """On a TPU, 'auto' means the paged kernels: a page size that cannot
    keep the decode kernel's block partition raises at construction,
    naming the geometry (CPU keeps the gather route)."""
    import jax
    import jax.numpy as jnp

    from mlcomp_tpu.engine import DecodeEngine
    from mlcomp_tpu.models import create_model
    from mlcomp_tpu.ops import pallas
    from mlcomp_tpu.train.state import init_model

    model = create_model({
        "name": "transformer_lm", "vocab_size": 64, "hidden": 32,
        "layers": 1, "heads": 2, "mlp_dim": 64, "kv_quant": True,
    })
    params, _ = init_model(
        model, {"x": jnp.zeros((1, 8), jnp.int32)}, jax.random.PRNGKey(0)
    )
    # 256 + 64 tokens -> a 384-slot int8 buffer with one 384-slot
    # kernel block: not a whole number of 256-token pages
    kw = dict(slots=2, prompt_buckets=(256,), max_new_cap=64,
              prefill_chunk=256, kv_layout="paged", kv_page_tokens=256)
    monkeypatch.setattr(pallas, "on_tpu", lambda: True)
    with pytest.raises(ValueError, match="256-token pages cannot"):
        DecodeEngine(model, {"params": params}, **kw)
    monkeypatch.undo()
    DecodeEngine(model, {"params": params}, **kw).close()


def test_native_core_reports_whether_it_loaded():
    from mlcomp_tpu import native

    st = native.status()
    assert st["loaded"] is (native.lib() is not None)
    if st["loaded"]:
        assert st["build_error"] is None
        # the binary's name is keyed by the sources it was built from
        assert native._so_path().exists()
        assert native._so_path().name.startswith("libmlcdata-")


def test_init_cache_traces_no_attention_kernels(monkeypatch):
    """init_cache wants the cache shapes only.  On a TPU backend the
    int8-KV module used to attend its buffer-wide init "chunk" through
    ceil(L/32) kernel tiles per layer — minutes of tracing per serve
    start at 16 layers, invisible on the CPU route."""
    import jax

    from mlcomp_tpu.models import create_model
    from mlcomp_tpu.models.generation import init_cache
    from mlcomp_tpu.ops.pallas import decode_attention

    model = create_model({
        "name": "transformer_lm", "vocab_size": 64, "hidden": 256,
        "layers": 2, "heads": 2, "mlp_dim": 64, "kv_quant": True,
    })
    monkeypatch.setattr(decode_attention, "on_tpu", lambda: True)
    jaxpr = jax.make_jaxpr(lambda: init_cache(model, 2, 384))()
    assert "pallas_call" not in str(jaxpr)
    leaves = jax.tree.leaves(jaxpr.out_avals)
    assert any(a.shape == (2, 2, 384, 128) for a in leaves)  # int8 K/V
