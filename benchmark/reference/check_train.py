"""Training's comparison: the first optimizer steps, followed in float32.

The reference takes the same seeded weights and the same rows as the
trainer and follows its first epoch (``steps`` optimizer steps, each on
rows of its own): forward, next-token loss, gradients by ``jax.grad``
of the plain model, all at ``highest`` precision, and the optimizer the
configuration names, from ``optax`` (a library both sides use; nothing
of the program is imported).  It runs before the trainer's state exists.

Numbers handed back, each a list over leaves in canonical order (every
layer's leaves, then the top's):

- ``loss``: mean of the epoch's step losses (what the trainer reports);
- ``grad_stat``: root of the mean of the optimizer's second-moment
  accumulator after the epoch — the gradients as the optimizer got them;
- ``delta_norm``: norm of each leaf's change over the epoch.

``round_fn`` is the control's hook: the same mathematics with every
product's operands rounded to float8, the nearest precision below the
configuration's bfloat16.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmark import cells
from benchmark import weights as W


def make_optimizer(opt_cfg: Dict[str, Any]):
    cfg = dict(opt_cfg)
    name = cfg.pop("name")
    if name != "adafactor":
        raise ValueError(f"the reference follows adafactor, not {name!r}")
    return optax.adafactor(learning_rate=float(cfg.pop("lr")), **cfg)


def _fac_state(opt_state):
    for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "v_row")
    ):
        if hasattr(s, "v_row"):
            return s
    raise RuntimeError("no factored second-moment state in the optimizer")


def leaf_stat(v_row, v_col, v, lead: int):
    """sqrt(mean(second moment)) of one leaf; ``lead`` leading axes are
    kept (the reference's stacked layers)."""
    acc = v_row if v_row.size > v.size else v
    axes = tuple(range(lead, acc.ndim))
    return jnp.sqrt(jnp.mean(acc, axis=axes))


def one_kind(M, d) -> str:
    """The kind of every layer.  This comparison stacks nothing, but it
    lists its numbers leaf by leaf in ONE canonical order
    (``LAYER_LEAVES``), so it follows architectures whose layers are all
    of one kind and refuses the others by name."""
    kinds = sorted(set(M.layer_kinds(d)))
    if len(kinds) != 1:
        raise SystemExit(
            f"the training comparison follows one kind of layer; this "
            f"architecture has {kinds} (the serve comparison walks them)"
        )
    return kinds[0]


def _row_layer(M, w, x_row, d, kind, rf):
    """One layer on one row (S, d) -> (S, d)."""
    pos = jnp.arange(x_row.shape[0], dtype=jnp.int32)[None]
    return M.layer(x_row[None], w, pos, d, kind, round_fn=rf)[0]


def _head_nll(M, top, x_row, tgt, d, rf, seq_chunk):
    """Summed next-token loss of one row; the head in sequence chunks,
    each rematerialized (float32 logits of the whole row at this
    vocabulary would be gigabytes)."""
    s, dm = x_row.shape
    xc = x_row.reshape(s // seq_chunk, seq_chunk, dm)
    tc = tgt.reshape(s // seq_chunk, seq_chunk)
    keep = (jnp.arange(s) < s - 1).reshape(tc.shape)

    @jax.checkpoint
    def chunk(xtk):
        xx, tt, kk = xtk
        lp = jax.nn.log_softmax(M.logits(xx, top, d, round_fn=rf), axis=-1)
        nll = -jnp.take_along_axis(lp, tt[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(kk, nll, 0.0))

    return jnp.sum(jax.lax.map(chunk, (xc, tc, keep)))


class Reference:
    """The trainer's first epoch, a layer at a time: activations of the
    forward pass are kept per layer, the backward pass walks the layers
    in reverse and hands each layer's gradient to the optimizer at once,
    so no second copy of the parameters' size ever exists."""

    def __init__(self, cfg: Dict[str, Any], seed: int, round_fn=None):
        # the architecture: the file the configuration's ``reference`` names
        self.arch = M = cells.architecture(cfg)
        self.d = d = M.dims_of(cfg)
        kind = one_kind(M, d)
        rf = round_fn if round_fn is not None else (lambda x: x)
        tx = self.tx = make_optimizer(cfg["trainer"]["optimizer"])
        self.key = W.seed_key(seed)
        seq = int(cfg["trainer"]["seq_len"])
        chunk = min(512, seq)

        self.init_layer = jax.jit(
            lambda key, i: M.layer_weights(key, i, d, jnp.float32, kind))
        self.init_top = jax.jit(lambda key: M.top_weights(key, d, jnp.float32))
        self.init_opt = jax.jit(tx.init)

        @jax.jit
        def fwd(w, x):
            return jax.lax.map(
                lambda r: _row_layer(M, w, r, d, kind, rf), x)

        @partial(jax.jit, donate_argnums=(0, 1))
        def bwd(w, st, x, dy):
            def one(carry, xr_dy):
                xr, dyr = xr_dy
                _, vjp = jax.vjp(
                    lambda w_, x_: _row_layer(M, w_, x_, d, kind, rf), w, xr)
                gw, dx = vjp(dyr)
                return jax.tree.map(jnp.add, carry, gw), dx

            zero = jax.tree.map(jnp.zeros_like, w)
            g, dx = jax.lax.scan(one, zero, (x, dy))
            up, st = tx.update(g, st, w)
            return optax.apply_updates(w, up), st, dx

        @jax.jit
        def top_step(top, ids, x):
            """Loss of the batch, its gradient at the last layer's output,
            and the head's and the embedding's update."""
            tgt = jnp.concatenate(
                [ids[:, 1:], jnp.zeros((ids.shape[0], 1), ids.dtype)], 1)
            n = ids.shape[0] * (ids.shape[1] - 1)

            def loss_of(head_part, x):
                t = {**top, **head_part}
                rows = jax.lax.map(
                    lambda xt: _head_nll(M, t, xt[0], xt[1], d, rf, chunk),
                    (x, tgt))
                return jnp.sum(rows) / n

            part = {k: top[k] for k in ("final_norm", "head")}
            loss, (g_part, dx) = jax.value_and_grad(loss_of, (0, 1))(part, x)
            return loss, g_part, dx

        @partial(jax.jit, donate_argnums=(0, 1))
        def top_update(top, st, g_part, ids, dx0):
            g_emb = jnp.zeros_like(top["emb"]).at[ids.reshape(-1)].add(
                dx0.reshape(-1, dx0.shape[-1]))
            g = {"emb": g_emb, **g_part}
            up, st = tx.update(g, st, top)
            return optax.apply_updates(top, up), st

        self.fwd, self.bwd = fwd, bwd
        self.top_step, self.top_update = top_step, top_update
        self.embed = jax.jit(lambda top, ids: M.embed(ids, top["emb"]))

        @jax.jit
        def layer_numbers(w, st, key, i):
            w0 = M.layer_weights(key, i, d, jnp.float32, kind)
            fac = _fac_state(st)
            return (
                [leaf_stat(fac.v_row[n], fac.v_col[n], fac.v[n], 0)
                 for n in M.LAYER_LEAVES],
                [jnp.sqrt(jnp.sum((w[n] - w0[n]) ** 2))
                 for n in M.LAYER_LEAVES],
            )

        @jax.jit
        def top_numbers(top, st, key):
            t0 = M.top_weights(key, d, jnp.float32)
            fac = _fac_state(st)
            return (
                [leaf_stat(fac.v_row[n], fac.v_col[n], fac.v[n], 0)
                 for n in M.TOP_LEAVES],
                [jnp.sqrt(jnp.sum((top[n] - t0[n]) ** 2))
                 for n in M.TOP_LEAVES],
            )

        self.layer_numbers, self.top_numbers = layer_numbers, top_numbers

    def follow(self, rows: np.ndarray, steps: int) -> Dict[str, Any]:
        d, key = self.d, self.key
        layers = [self.init_layer(key, jnp.int32(i))
                  for i in range(d["layers"])]
        states = [self.init_opt(w) for w in layers]
        top = self.init_top(key)
        top_st = self.init_opt(top)
        batch = rows.shape[0] // steps
        losses = []
        for k in range(steps):
            ids = jnp.asarray(rows[k * batch:(k + 1) * batch])
            x = self.embed(top, ids)
            acts = []
            for w in layers:
                acts.append(x)
                x = self.fwd(w, x)
            loss, g_part, dx = self.top_step(top, ids, x)
            losses.append(loss)
            for i in reversed(range(d["layers"])):
                layers[i], states[i], dx = self.bwd(
                    layers[i], states[i], acts.pop(), dx)
            top, top_st = self.top_update(top, top_st, g_part, ids, dx)
        gstat, dnorm = [], []
        for i in range(d["layers"]):
            g, n = self.layer_numbers(layers[i], states[i], key, jnp.int32(i))
            gstat += [float(v) for v in g]
            dnorm += [float(v) for v in n]
        g, n = self.top_numbers(top, top_st, key)
        gstat += [float(v) for v in g]
        dnorm += [float(v) for v in n]
        return {
            "loss": float(np.mean([float(v) for v in losses])),
            "grad_stat": gstat, "delta_norm": dnorm,
        }


def train_reference(cfg: Dict[str, Any], seed: int, rows: np.ndarray,
                    steps: int, round_fn=None) -> Dict[str, Any]:
    return Reference(cfg, seed, round_fn).follow(rows, steps)


