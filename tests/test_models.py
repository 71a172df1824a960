"""Shape/grad/finiteness tests across the model zoo (tiny configs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import pallas_calls

from mlcomp_tpu.models import create_model


def _init_and_forward(model, x, train=False):
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    if train and "batch_stats" in variables:
        out, _ = model.apply(variables, x, train=True, mutable=["batch_stats"])
    else:
        out = model.apply(variables, x, train=train)
    return variables, out


def test_resnet50_shapes_and_finite():
    m = create_model({"name": "resnet50", "num_classes": 10, "width": 16, "dtype": "float32"})
    x = jnp.ones((2, 64, 64, 3))
    variables, out = _init_and_forward(m, x)
    assert out.shape == (2, 10)
    assert out.dtype == jnp.float32
    assert "batch_stats" in variables  # BN statistics tracked
    assert np.all(np.isfinite(np.asarray(out)))


def test_resnet_train_mode_updates_stats():
    m = create_model({"name": "resnet18", "num_classes": 4, "width": 8, "dtype": "float32"})
    x = jnp.asarray(np.random.RandomState(0).rand(2, 32, 32, 3), jnp.float32)
    variables = m.init(jax.random.PRNGKey(0), x, train=False)
    _, updated = m.apply(variables, x, train=True, mutable=["batch_stats"])
    before = jax.tree.leaves(variables["batch_stats"])[0]
    after = jax.tree.leaves(updated["batch_stats"])[0]
    assert not np.allclose(np.asarray(before), np.asarray(after))


def test_unet_shapes():
    m = create_model(
        {"name": "unet", "num_classes": 5, "features": [8, 16, 32], "dtype": "float32"}
    )
    x = jnp.ones((2, 64, 64, 3))
    _, out = _init_and_forward(m, x)
    assert out.shape == (2, 64, 64, 5)
    assert out.dtype == jnp.float32


def test_bert_classifier_and_mlm():
    cfg = dict(vocab_size=100, hidden=32, layers=2, heads=2, mlp_dim=64, max_len=16, dtype="float32")
    x = jnp.asarray(np.random.RandomState(0).randint(1, 100, (2, 16)))
    m = create_model({"name": "bert", "num_classes": 3, **cfg})
    _, out = _init_and_forward(m, x)
    assert out.shape == (2, 3)
    mlm = create_model({"name": "bert", "num_classes": None, **cfg})
    _, out2 = _init_and_forward(mlm, x)
    assert out2.shape == (2, 16, 100)


def test_bert_padding_mask_blocks_pad_influence():
    """Changing the NUMBER of trailing pad (id 0) slots vs real-token slots
    must change output, while the masked pads themselves must not leak into
    the CLS representation: compare same real prefix with different garbage
    beyond an attention-masked region by toggling a real token instead."""
    cfg = dict(vocab_size=50, hidden=16, layers=1, heads=2, mlp_dim=32, max_len=8, dtype="float32")
    m = create_model({"name": "bert", "num_classes": 2, **cfg})
    rs = np.random.RandomState(0)
    real = rs.randint(1, 50, (1, 4))
    a = np.concatenate([real, np.zeros((1, 4), int)], axis=1)  # 4 real + 4 pad
    variables = m.init(jax.random.PRNGKey(0), jnp.asarray(a), train=False)
    out_a = np.asarray(m.apply(variables, jnp.asarray(a), train=False))
    # pads are masked: CLS output must not depend on how many pads follow
    a_short = np.concatenate([real, np.zeros((1, 2), int)], axis=1)
    out_short = np.asarray(m.apply(variables, jnp.asarray(a_short), train=False))
    assert np.allclose(out_a, out_short, atol=1e-5)
    # real tokens are NOT masked: changing one must change the output
    b = a.copy()
    b[0, 2] = (b[0, 2] % 49) + 1
    out_b = np.asarray(m.apply(variables, jnp.asarray(b), train=False))
    assert not np.allclose(out_a, out_b, atol=1e-5)


def test_transformer_lm_causality():
    cfg = {"name": "transformer_lm", "vocab_size": 64, "hidden": 32, "layers": 2,
           "heads": 4, "dtype": "float32"}
    m = create_model(cfg)
    rs = np.random.RandomState(0)
    x1 = rs.randint(0, 64, (1, 12))
    x2 = x1.copy()
    x2[0, -1] = (x2[0, -1] + 1) % 64  # change ONLY the last token
    variables = m.init(jax.random.PRNGKey(0), jnp.asarray(x1), train=False)
    o1 = np.asarray(m.apply(variables, jnp.asarray(x1), train=False))
    o2 = np.asarray(m.apply(variables, jnp.asarray(x2), train=False))
    # causal: logits at positions < last must be unchanged
    assert np.allclose(o1[0, :-1], o2[0, :-1], atol=1e-5)
    assert not np.allclose(o1[0, -1], o2[0, -1])


def test_transformer_gqa():
    cfg = {"name": "transformer_lm", "vocab_size": 64, "hidden": 32, "layers": 1,
           "heads": 4, "kv_heads": 2, "dtype": "float32"}
    m = create_model(cfg)
    x = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 8)))
    _, out = _init_and_forward(m, x)
    assert out.shape == (2, 8, 64)


def test_models_have_gradients():
    m = create_model({"name": "resnet50", "num_classes": 4, "width": 8, "dtype": "float32"})
    # random input: constant input would be zeroed by train-mode BN
    x = jnp.asarray(np.random.RandomState(0).rand(2, 32, 32, 3), jnp.float32)
    variables = dict(m.init(jax.random.PRNGKey(0), x, train=False))
    params = variables.pop("params")

    def loss(p):
        out, _ = m.apply(
            {"params": p, **variables}, x, train=True, mutable=["batch_stats"]
        )
        return jnp.mean(out**2)

    grads = jax.grad(loss)(params)
    norms = [float(jnp.abs(g).max()) for g in jax.tree.leaves(grads)]
    assert all(np.isfinite(n) for n in norms)
    assert any(n > 0 for n in norms)


def test_vit_forward_and_trains():
    from mlcomp_tpu.train.loop import Trainer

    cfg = {
        "model": {"name": "vit_tiny", "num_classes": 4, "patch": 8,
                  "dtype": "float32"},
        "optimizer": {"name": "lars", "lr": 0.1},
        "loss": "cross_entropy",
        "metrics": ["accuracy"],
        "epochs": 1,
        "data": {
            "train": {"name": "synthetic_images", "n": 16, "image": 32,
                      "num_classes": 4, "batch_size": 8}
        },
    }
    tr = Trainer(cfg)
    stats = tr.train_epoch()
    assert np.isfinite(stats["loss"])


def test_vit_cls_pooling():
    import jax
    from mlcomp_tpu.models import create_model
    from mlcomp_tpu.train.state import init_model

    m = create_model({"name": "vit_tiny", "num_classes": 3, "patch": 8,
                      "pool": "cls", "dtype": "float32"})
    x = jnp.zeros((2, 32, 32, 3))
    params, state = init_model(m, {"x": x}, jax.random.PRNGKey(0))
    out = m.apply({"params": params, **state}, x)
    assert out.shape == (2, 3)


def test_lars_optimizer_builds():
    from mlcomp_tpu.train.optim import create_optimizer

    tx = create_optimizer({"name": "lars", "lr": 0.5, "weight_decay": 1e-4})
    assert tx is not None


def _remat_lm(monkeypatch, through_flash=True, **over):
    """A two-layer LM with and without ``remat``, its parameters and a
    loss over them; ``through_flash`` at a length the flash kernel takes
    (interpret mode on the CPU), else on the XLA path."""
    from mlcomp_tpu.train.state import init_model

    if through_flash:
        monkeypatch.setenv("MLCOMP_TPU_FLASH", "1")
    cfg = {"name": "transformer_lm", "vocab_size": 32, "hidden": 32,
           "layers": 2, "heads": 2, "kv_heads": 1, "dtype": "float32", **over}
    x = jnp.asarray(np.random.RandomState(0).randint(
        1, 32, (2, 128 if through_flash else 8)))
    plain = create_model(cfg)
    params, _ = init_model(plain, {"x": x}, jax.random.PRNGKey(0))

    def loss(m, p):
        return jnp.sum(m.apply({"params": p}, x) ** 2)

    return plain, create_model({**cfg, "remat": True}), params, loss


@pytest.mark.parametrize("through_flash", [False, True])
def test_transformer_remat_matches_plain(monkeypatch, through_flash):
    """remat=True changes memory, not math: forward and gradients match,
    where the whole layer is recomputed and where it keeps the flash
    kernel's residuals."""
    plain, remat, params, loss = _remat_lm(monkeypatch, through_flash)
    np.testing.assert_allclose(
        float(loss(plain, params)), float(loss(remat, params)), rtol=1e-6
    )
    gp = jax.grad(lambda p: loss(plain, p))(params)
    gr = jax.grad(lambda p: loss(remat, p))(params)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("names, fwd_calls_a_layer", [
    (None, 1),          # the list that ships
    (("flash_out", "flash_lse"), 1),
    (("flash_lse",), 2),  # out is needed downstream: the call comes back
    ((), 2),            # a plain remat runs the forward kernel again
])
def test_remat_keeps_the_flash_kernels_outputs(monkeypatch, names,
                                               fwd_calls_a_layer):
    """One forward kernel call a layer in the whole gradient program,
    none of them in the rematerialised part; a policy without the
    kernel's names (what a later edit that drops one gives) has two."""
    from mlcomp_tpu.ops.pallas import flash_attention

    if names is not None:
        monkeypatch.setattr(flash_attention, "REMAT_SAVED_NAMES", names)
    _, remat, params, loss = _remat_lm(monkeypatch)
    calls = pallas_calls(
        jax.make_jaxpr(jax.grad(lambda p: loss(remat, p)))(params).jaxpr
    )
    fwd = [c for c in calls if c[1].startswith("flash_fwd")]
    bwd = [c for c in calls if c[1].startswith("flash_dq")]
    assert len(bwd) == 2 and all("remat2" in c[0] for c in bwd)
    assert len(fwd) == 2 * fwd_calls_a_layer
    again = [c for c in fwd if "remat2" in c[0]]
    assert len(again) == 2 * (fwd_calls_a_layer - 1)


def test_remat_keeps_the_flash_kernels_outputs_under_a_mesh(monkeypatch):
    """Under a dp/tp mesh the kernel sits in a shard_map island; the
    layer's policy reaches the names inside it."""
    from mlcomp_tpu.parallel.mesh import MeshSpec, make_mesh, set_current_mesh

    set_current_mesh(make_mesh(MeshSpec(dp=2, tp=2), devices=jax.devices()[:4]))
    try:
        _, remat, params, loss = _remat_lm(monkeypatch, kv_heads=2)
        calls = pallas_calls(
            jax.make_jaxpr(jax.grad(lambda p: loss(remat, p)))(params).jaxpr
        )
    finally:
        set_current_mesh(None)
    # a layer: the forward kernel and the one backward kernel
    assert all("shard_map" in c[0] for c in calls) and len(calls) == 4
    fwd = [c for c in calls if c[1].startswith("flash_fwd")]
    assert len(fwd) == 2 and not any("remat2" in c[0] for c in fwd)


# ---- last_logits_only: the final norm and the head on the row kept ----

@pytest.mark.parametrize("int8_fold_norms", [False, True])
@pytest.mark.parametrize("kv_quant", [False, True])
def test_transformer_lm_last_logits_only(kv_quant, int8_fold_norms):
    """Two chunks of 2 x 40 rows: over 64 rows the full call's final
    norm runs on its own, while the keyword's 2 rows take the folded
    norm in the int8 head's prologue — the served case.  That pair is
    held to the folded kernel's own tolerance (bfloat16 products,
    another reduce order), the float32 head to float32's."""
    from mlcomp_tpu.ops.quant import quantize_params
    from mlcomp_tpu.train.state import init_model
    from helpers_last_logits import assert_last_logits_only_is_the_last_row

    model = create_model({
        "name": "transformer_lm", "vocab_size": 256, "hidden": 128,
        "layers": 2, "heads": 2, "mlp_dim": 256, "dtype": "float32",
        "kv_quant": kv_quant,
    })
    ids = jnp.asarray(np.random.RandomState(3).randint(1, 256, (2, 80)))
    params, _ = init_model(model, {"x": ids[:, :8]}, jax.random.PRNGKey(0))
    if int8_fold_norms:
        assert type(model).fold_norms_eligible
        params = quantize_params(params, min_size=1024)
    tol = 2e-2 if int8_fold_norms else 1e-5
    assert_last_logits_only_is_the_last_row(
        model, {"params": params}, ids, chunk=40, l_buf=96,
        intercept=int8_fold_norms, rtol=tol, atol=tol)


@pytest.mark.parametrize("kv_quant", [False, True])
def test_moe_lm_last_logits_only(kv_quant):
    """The third decoder ``generate`` and the engine serve takes the
    keyword too: they pass it to whatever model they are given."""
    from helpers_last_logits import assert_last_logits_only_is_the_last_row
    from mlcomp_tpu.train.state import init_model

    model = create_model({
        "name": "moe_lm", "vocab_size": 64, "hidden": 32, "layers": 2,
        "heads": 2, "n_experts": 4, "moe_every": 2, "dtype": "float32",
        "kv_quant": kv_quant,
    })
    ids = jnp.asarray(np.random.RandomState(4).randint(1, 64, (2, 16)))
    params, _ = init_model(model, {"x": ids[:, :8]}, jax.random.PRNGKey(0))
    assert_last_logits_only_is_the_last_row(
        model, {"params": params}, ids, chunk=8, l_buf=24)
