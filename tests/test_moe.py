"""MoE routing correctness + ep-sharded training on the CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlcomp_tpu.models import create_model
from mlcomp_tpu.models.moe import MoEBlock


def test_moe_block_routes_and_sows_aux():
    block = MoEBlock(n_experts=4, d_model=16, d_ff=32, k=2,
                     capacity_factor=2.0, dtype=jnp.float32)
    x = jnp.asarray(np.random.RandomState(0).normal(size=(2, 8, 16)), jnp.float32)
    variables = dict(block.init(jax.random.PRNGKey(0), x))
    variables.pop("losses", None)  # same as train.state.init_model
    out, state = block.apply(variables, x, train=True, mutable=["losses"])
    assert out.shape == x.shape
    aux = jax.tree.leaves(state["losses"])
    assert len(aux) == 1 and np.isfinite(float(aux[0]))
    # with generous capacity almost no tokens drop; output should be nonzero
    assert float(jnp.abs(out).mean()) > 1e-4


def test_moe_capacity_drops_tokens():
    # TRAINING with capacity 1 slot/expert: most tokens dropped -> output
    # rows mostly zero.  INFERENCE routes densely: same block, same tiny
    # capacity factor, but no token may be dropped (KV-cache decode parity
    # depends on this).
    block = MoEBlock(n_experts=2, d_model=8, d_ff=16, k=1,
                     capacity_factor=0.1, dtype=jnp.float32)
    x = jnp.asarray(np.random.RandomState(1).normal(size=(1, 32, 8)), jnp.float32)
    variables = block.init(jax.random.PRNGKey(0), x)
    out, _ = block.apply(variables, x, train=True, mutable=["losses"])
    row_norms = np.asarray(jnp.linalg.norm(out[0], axis=-1))
    assert (row_norms < 1e-6).sum() >= 28  # ~2 slots of 32 survive
    dense = block.apply(variables, x, train=False)
    dense_norms = np.asarray(jnp.linalg.norm(dense[0], axis=-1))
    assert (dense_norms > 1e-6).all()  # drop-free at inference


def test_moe_lm_forward():
    model = create_model({
        "name": "moe_lm", "vocab_size": 64, "hidden": 32, "layers": 2,
        "heads": 4, "n_experts": 4, "d_ff": 64, "moe_every": 2,
        "dtype": "float32",
    })
    x = jnp.asarray(np.random.RandomState(2).randint(0, 64, (2, 16)))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 16, 64)


def test_moe_lm_trains_with_ep_sharding():
    from mlcomp_tpu.train.loop import Trainer

    cfg = {
        "model": {"name": "moe_lm", "vocab_size": 64, "hidden": 32,
                  "layers": 2, "heads": 4, "n_experts": 4, "d_ff": 64,
                  "moe_every": 2, "dtype": "float32"},
        "optimizer": {"name": "adam", "lr": 1e-3},
        "loss": "lm_cross_entropy",
        "metrics": [],
        "epochs": 1,
        "mesh": {"dp": 2, "ep": 4},
        "data": {
            "train": {"name": "synthetic_tokens", "n": 32, "seq_len": 16,
                      "vocab_size": 64, "batch_size": 16},
        },
    }
    tr = Trainer(cfg)
    w1 = tr.state.params["MoELayer_0"]["moe"]["experts_w1"]
    assert "ep" in w1.sharding.spec, w1.sharding.spec
    stats = tr.train_epoch()
    assert np.isfinite(stats["loss"])


def test_moe_dense_einsum_matches_scan_to_tolerance():
    """r4 advisor (low): the t<=64 dense einsum and the per-expert scan
    accumulate the combine in different float orders, so a token decoded
    one step at a time (einsum path) tracks its full-forward value (scan
    path at t>64) to dtype tolerance — not bit-exactly.  Dense routing
    is per-token, so the same token in a longer batch routes the same."""
    block = MoEBlock(n_experts=4, d_model=16, d_ff=32, k=2,
                     capacity_factor=2.0, dtype=jnp.float32)
    x_long = jnp.asarray(
        np.random.RandomState(7).normal(size=(1, 96, 16)), jnp.float32
    )
    variables = block.init(jax.random.PRNGKey(0), x_long)
    out_scan = block.apply(variables, x_long, train=False)       # t=96: scan
    out_einsum = block.apply(variables, x_long[:, :32], train=False)  # t=32
    np.testing.assert_allclose(
        np.asarray(out_einsum), np.asarray(out_scan[:, :32]),
        rtol=2e-5, atol=2e-5,
    )


def test_routed_experts_default_router_is_the_softmax_it_was():
    """``router_score="softmax"`` without a selection bias, the
    defaults, is the program ``RoutedExperts`` was before the field:
    the same parameters (no ``router_bias``), the same jaxpr as the
    module given the two defaults by name, the sigmoid router's one
    logistic function not in it, and the old rule (the top k of the softmax, renormalised) written
    out densely."""
    from mlcomp_tpu.models.moe import ROUTER_SCORES, RoutedExperts

    kw = dict(n_experts=8, d_model=128, d_ff=128, k=2, routed_scale=2.5,
              shared_width=128, dtype=jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 128))
    as_it_was, named = RoutedExperts(**kw), RoutedExperts(
        **kw, router_score="softmax", selection_bias=False)
    params = as_it_was.init(jax.random.PRNGKey(1), u)["params"]
    assert sorted(params) == [
        "experts_down", "experts_gate", "experts_up", "router",
        "shared_down", "shared_gate", "shared_up"]
    assert sorted(ROUTER_SCORES) == ["sigmoid", "softmax"]
    import re

    def program(layer):
        # a kernel's jaxpr prints its functions' addresses: not the program
        return re.sub(r"0x[0-9a-f]+", "", str(jax.make_jaxpr(
            lambda p, x: layer.apply({"params": p}, x))(params, u)))

    text = program(as_it_was)
    assert text == program(named)
    sig = program(RoutedExperts(**kw, router_score="sigmoid"))
    # the logistic function is in both (SiLU): once more where it scores
    assert sig.count("logistic") == text.count("logistic") + 1
    assert "softmax" not in sig and "exp" in text
    with jax.default_matmul_precision("highest"):
        got = as_it_was.apply({"params": params}, u)
        np.testing.assert_array_equal(got, named.apply({"params": params}, u))
        probs = jax.nn.softmax(u @ params["router"]["kernel"], axis=-1)
        topv, topi = jax.lax.top_k(probs, 2)
        gates = topv / topv.sum(-1, keepdims=True) * 2.5
        weight = (jax.nn.one_hot(topi, 8) * gates[..., None]).sum(-2)
        unit = lambda x, g, up, down: (  # noqa: E731
            jax.nn.silu(x @ g) * (x @ up)) @ down
        want = sum(
            weight[..., e:e + 1] * unit(u, params["experts_gate"][e],
                                        params["experts_up"][e],
                                        params["experts_down"][e])
            for e in range(8)
        ) + unit(u, *(params[f"shared_{n}"]["kernel"]
                      for n in ("gate", "up", "down")))
    np.testing.assert_allclose(got, want, atol=2e-5)


def _grouped_matmul_grids(layer, params, x):
    """(grid, rows-block shape) of every ``grouped_matmul`` kernel call
    in the traced layer call."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                gm = eqn.params["grid_mapping"]
                found.append((tuple(gm.grid), tuple(
                    getattr(b, "block_size", b)
                    for b in gm.block_mappings[0].block_shape)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(
        lambda p, u: layer.apply({"params": p}, u))(params, x).jaxpr)
    return found


def test_routed_experts_in_large_tiles_is_its_dense_reference():
    """256 tokens to 2 of 4 experts are 128 rows an expert: the layout
    takes 128-row tiles (``auto_row_tile``), and the layer is still the
    dense rule written out: every token's top two, renormalised."""
    from mlcomp_tpu.models.moe import RoutedExperts
    from mlcomp_tpu.ops.pallas.grouped_matmul import auto_row_tile

    layer = RoutedExperts(n_experts=4, d_model=128, d_ff=128, k=2,
                          dtype=jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 128, 128))
    params = layer.init(jax.random.PRNGKey(1), u)["params"]
    assert auto_row_tile(256, 2, 4) == 128
    grids = _grouped_matmul_grids(layer, params, u)
    assert len(grids) == 2 and all(
        block[0] == 128 and grid[1] * 128 == 512 + 4 * 128
        for grid, block in grids)
    with jax.default_matmul_precision("highest"):
        got, upd = layer.apply({"params": params}, u, mutable=["counters"])
        probs = jax.nn.softmax(u @ params["router"]["kernel"], axis=-1)
        topv, topi = jax.lax.top_k(probs, 2)
        gates = topv / topv.sum(-1, keepdims=True)
        weight = (jax.nn.one_hot(topi, 4) * gates[..., None]).sum(-2)
        want = sum(
            weight[..., e:e + 1] * (
                (jax.nn.silu(u @ params["experts_gate"][e])
                 * (u @ params["experts_up"][e])) @ params["experts_down"][e])
            for e in range(4))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # the tiles it used, counted: each expert's rows rounded up to 128
    sizes = np.bincount(np.asarray(topi).reshape(-1), minlength=4)
    counts = np.asarray(upd["counters"]["moe"])
    assert counts.shape == (13,)
    assert counts[9] == counts[10] == sum(-(-s // 128) * 128 for s in sizes)
    assert counts[1] == counts[6] == 512
    # no zero-compute expert to choose
    assert counts[11] == counts[12] == 0


def test_a_laguna_shaped_call_is_laid_out_as_it_was(monkeypatch):
    """A decode step (48 tokens) and a 256-token chunk, 10 of 256
    experts of which 128 are held: 2 and 10 rows an expert keep the
    16-row tiles and the buffers they had, and the traced program is the
    one a constant 16 gives, word for word."""
    import re

    from mlcomp_tpu.models.moe import RoutedExperts
    from mlcomp_tpu.ops.pallas import grouped_matmul as gm

    layer = RoutedExperts(n_experts=256, d_model=128, d_ff=128, k=10,
                          experts_held=(0, 128), shared_width=128,
                          dtype=jnp.float32)

    def program(u, params):
        return re.sub(r"0x[0-9a-f]+", "", str(jax.make_jaxpr(
            lambda p, x: layer.apply({"params": p}, x))(params, u)))

    for shape in ((48, 1, 128), (1, 256, 128)):
        u = jax.random.normal(jax.random.PRNGKey(0), shape)
        params = jax.eval_shape(
            lambda: layer.init(jax.random.PRNGKey(1), u))["params"]
        a = shape[0] * shape[1] * 10
        grids = _grouped_matmul_grids(layer, params, u)
        assert len(grids) == 2 and all(
            block[0] == 16 and grid[1] * 16 == gm.padded_rows(a, 128, 16)
            for grid, block in grids)
        text = program(u, params)
        with monkeypatch.context() as m:
            m.setattr(gm, "auto_row_tile", lambda *_: gm.ROW_TILE)
            assert program(u, params) == text


@pytest.mark.parametrize("zero,renormalise", [
    (0, True), (0, False), (4, True), (4, False)],
    ids=["as_it_was", "raw_weights", "zero_experts", "longcat"])
def test_zero_experts_and_raw_weights_are_the_rule_written_out(
        zero, renormalise):
    """The router is ``n_experts + zero_experts`` wide; a chosen output
    past the real experts adds ``weight x input`` and no row; without
    ``renormalise`` the weights are the scores times the scale.  Against
    the rule token by token in numpy, of which the first case is the
    layer as it was (both new fields at their defaults)."""
    from mlcomp_tpu.models.moe import RoutedExperts

    n, k, h, scale = 8, 3, 128, 6.0
    fields = {} if (zero, renormalise) == (0, True) else {
        "zero_experts": zero, "renormalise": renormalise}
    layer = RoutedExperts(n_experts=n, d_model=h, d_ff=128, k=k,
                          routed_scale=scale, dtype=jnp.float32,
                          selection_bias=True, **fields)
    u = jax.random.normal(jax.random.PRNGKey(0), (1, 12, h))
    params = layer.init(jax.random.PRNGKey(1), u)["params"]
    assert params["router"]["kernel"].shape == (h, n + zero)
    bias = 0.05 * np.sin(np.arange(n + zero, dtype=np.float64))
    params = {**params, "router_bias": jnp.asarray(bias, jnp.float32)}
    with jax.default_matmul_precision("highest"):
        got, upd = layer.apply({"params": params}, u, mutable=["counters"])
    un = np.asarray(u[0], np.float64)
    w = {name: np.asarray(v, np.float64) for name, v in params.items()
         if name.startswith("experts")}
    logits = un @ np.asarray(params["router"]["kernel"], np.float64)
    s = np.exp(logits - logits.max(-1, keepdims=True))
    s /= s.sum(-1, keepdims=True)
    want, to_zero = np.zeros_like(un), 0
    for t in range(12):
        top = np.argsort(-(s[t] + bias))[:k]
        for e in top:
            g = scale * s[t, e] / (s[t, top].sum() if renormalise else 1.0)
            if e >= n:
                want[t] += g * un[t]
                to_zero += 1
                continue
            gate = un[t] @ w["experts_gate"][e]
            act = gate / (1.0 + np.exp(-gate)) * (un[t] @ w["experts_up"][e])
            want[t] += g * (act @ w["experts_down"][e])
    np.testing.assert_allclose(np.asarray(got)[0], want, atol=2e-5)
    counts = np.asarray(upd["counters"]["moe"])
    assert counts[0] == 12 * k and counts[1] == 12 * k - to_zero
    # a single row of 12 tokens is a chunk call
    assert counts[11] == counts[12] == to_zero
    assert (to_zero > 0) == (zero > 0)
