"""Mixture-of-Experts transformer LM — expert parallelism over ``ep``.

No MoE exists in the reference (its model surface is torchvision-era);
this family exists to make the ``ep`` mesh axis a real, exercised
capability. TPU-first design choices:

- Switch/Mesh-TF style STATIC dispatch: top-k routing materialized as
  dense one-hot dispatch/combine tensors and einsums — fixed shapes, no
  sorts or gathers, so XLA tiles everything onto the MXU and inserts the
  token all-to-all implicitly when expert weights are sharded over ep;
- stacked expert weights ``experts_w1: (E, d, f)`` / ``experts_w2:
  (E, f, d)`` shard over ``ep`` (and ``f`` over ``tp``) via
  parallel/sharding.py rules;
- capacity-factor token dropping (overflow tokens pass through the
  residual untouched) keeps shapes static under any routing skew;
- router in fp32 (routing decisions are precision-sensitive), experts in
  the model dtype;
- the load-balance auxiliary loss is ``sow``-ed into the ``losses``
  collection; the train step adds every sown loss to the objective.
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from mlcomp_tpu.models import MODELS
from mlcomp_tpu.models.counts import count_group
from mlcomp_tpu.models.transformer import DecoderLayer, RMSNorm


class MoEBlock(nn.Module):
    """Top-k routed expert FFN over flattened (tokens, d) activations."""

    n_experts: int
    d_model: int
    d_ff: int
    k: int = 2
    capacity_factor: float = 1.25
    dtype: jnp.dtype = jnp.bfloat16
    aux_weight: float = 0.01

    @nn.compact
    def __call__(self, x, train: bool = False):
        b, s, d = x.shape
        t = b * s
        e = self.n_experts
        cap = max(1, int(self.capacity_factor * t * self.k / e))
        tokens = x.reshape(t, d)

        # fp32 router — tiny matmul, decision quality matters
        logits = nn.Dense(e, use_bias=False, dtype=jnp.float32, name="router")(
            tokens.astype(jnp.float32)
        )
        probs = jax.nn.softmax(logits, axis=-1)                      # (T, E)

        from mlcomp_tpu.ops.quant import is_quantized_leaf

        w1 = self.param(
            "experts_w1",
            nn.initializers.normal(0.02),
            (e, d, self.d_ff),
            jnp.float32,
        )
        w2 = self.param(
            "experts_w2",
            nn.initializers.normal(0.02),
            (e, self.d_ff, d),
            jnp.float32,
        )
        # int8 decode: stacked expert weights may arrive quantized
        # ({"q8": (E, in, out) int8, "q8_scale": (E, 1, out)} — per-expert
        # per-channel scales, so each expert's 2-D slice feeds the Pallas
        # kernel directly in the inference scan).  Measured on v5e (638M
        # moe_lm, B=4, interleaved medians): throughput NEUTRAL vs bf16
        # (3.48 vs 3.43 ms/tok — per-call kernel overhead in the E-step
        # scan offsets the halved read), but weight HBM RESIDENCY halves
        # (entry dequant would materialize the bf16 copy), so the int8
        # path is the serving-density option: ~2x more MoE weights per
        # chip.
        quantized = is_quantized_leaf(w1)
        if quantized and train:
            raise ValueError("int8 expert weights are decode-only")
        if not quantized:
            w1 = w1.astype(self.dtype)
            w2 = w2.astype(self.dtype)

        if not train:
            # Inference is DROP-FREE: capacity competition exists for
            # training throughput, but its drop pattern depends on the
            # token count — a single-token decode step (T = B) and the
            # same token inside a full forward (T = B*S) would drop
            # differently, so KV-cache generation could diverge from the
            # full forward.  Dense routing (every expert on every token,
            # top-k combine) restores ROUTING equivalence; at decode
            # shapes the FFN is tiny, and eval pays e/k× FFN FLOPs for
            # determinism.  (Numerically the two dense paths below — the
            # t<=64 einsum and the per-expert scan — accumulate the
            # combine in different float orders, so a token decoded one
            # step at a time agrees with its full-forward value to
            # dtype tolerance, not bit-exactly; test_moe pins this.)
            topv, topi = jax.lax.top_k(probs, self.k)                # (T, k)
            gates = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
            weight = (
                jax.nn.one_hot(topi, e, dtype=jnp.float32)
                * gates[..., None]
            ).sum(1)                                                 # (T, E)
            toks = tokens.astype(self.dtype)

            if not quantized and t <= 64:
                # decode-step token counts: keep the expert axis WHOLE
                # in one einsum — the (E, T, F) intermediate is tiny at
                # these shapes, and ep-sharded expert weights then
                # compute their local experts in place with one psum for
                # the combine (the slice-scan below would instead
                # all-gather every expert slice under an ep mesh).
                # Multi-chip MoE serving runs through here.
                h_all = jax.nn.gelu(jnp.einsum("td,edf->etf", toks, w1))
                out = jnp.einsum(
                    "etf,efd,te->td", h_all, w2,
                    weight.astype(self.dtype),
                )
                return out.reshape(b, s, d)

            # scan one expert at a time: peak intermediate is (T, d_ff),
            # not (T, E, d_ff) — dense routing must not spike eval memory
            # E× past what a training step uses
            if quantized:
                from mlcomp_tpu.ops.quant import expert_matmul

                mm = lambda a, w: expert_matmul(a, w, self.dtype)  # noqa: E731
            else:
                mm = lambda a, w: a @ w                            # noqa: E731

            def one_expert(acc, wse):
                w1_e, w2_e, we = wse
                h_e = jax.nn.gelu(mm(toks, w1_e))                  # (T, F)
                return acc + we[:, None].astype(self.dtype) * (
                    mm(h_e, w2_e)
                ), None

            out, _ = jax.lax.scan(
                one_expert,
                jnp.zeros((t, d), self.dtype),
                (w1, w2, weight.T),
            )
            return out.reshape(b, s, d)

        # top-k dispatch with per-expert positions under a fixed capacity:
        # round r assigns every token its r-th-best expert; a token's slot is
        # (# earlier tokens routed to that expert, across all rounds so far)
        combine = jnp.zeros((t, e, cap), jnp.float32)
        remaining = probs
        filled = jnp.zeros((e,), jnp.float32)   # slots used per expert
        for _ in range(self.k):
            idx = jnp.argmax(remaining, axis=-1)                     # (T,)
            onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)       # (T, E)
            gate = (remaining * onehot).sum(-1)                      # (T,)
            pos = jnp.cumsum(onehot, axis=0) - onehot + filled[None] # (T, E)
            pos_tok = (pos * onehot).sum(-1).astype(jnp.int32)       # (T,)
            fits = (pos_tok < cap).astype(jnp.float32)
            keep = fits * gate
            combine = combine + (
                onehot[:, :, None]
                * jax.nn.one_hot(pos_tok, cap, dtype=jnp.float32)[:, None, :]
                * keep[:, None, None]
            )
            # only KEPT tokens occupy slots; dropped ones must not eat
            # capacity from later rounds
            filled = filled + (onehot * fits[:, None]).sum(axis=0)
            remaining = remaining * (1.0 - onehot)

        # GShard-style gate renormalization over the experts that kept the
        # token; fully-dropped tokens contribute 0 (residual passthrough)
        denom = combine.sum(axis=(1, 2), keepdims=True)
        combine = jnp.where(denom > 0.0, combine / jnp.maximum(denom, 1e-9), 0.0)
        dispatch = (combine > 0.0).astype(self.dtype)                # (T, E, C)

        # load-balance aux loss (Switch eq. 4): E * sum_e f_e * p_e
        me = probs.mean(axis=0)                                      # (E,)
        ce = dispatch.sum(axis=(0, 2)) / jnp.maximum(dispatch.sum(), 1.0)
        aux = self.aux_weight * e * jnp.sum(me * ce)
        self.sow("losses", "moe_aux", aux)

        expert_in = jnp.einsum("tec,td->ecd", dispatch, tokens.astype(self.dtype))
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", expert_in, w1))
        expert_out = jnp.einsum("ecf,efd->ecd", h, w2)
        out = jnp.einsum(
            "tec,ecd->td", combine.astype(self.dtype), expert_out
        )
        return out.reshape(b, s, d)


# how a router's logits become an expert's score
ROUTER_SCORES = {
    "softmax": lambda logits: jax.nn.softmax(logits, axis=-1),
    "sigmoid": jax.nn.sigmoid,
}


def _counts_block(sums, issued):
    calls, touched = sums["expert_layer_calls"], sums["experts_touched"]
    if not calls:
        return None
    # the entries that have a ``chunk_`` twin: counted by call class
    chunk = {
        name[len("chunk_"):]: c for name, c in sums.items()
        if name.startswith("chunk_")
    }
    return {
        "assignments": sums["assignments"],
        "assignments_held": sums["assignments_held"],
        "zero_assignments": sums["zero_assignments"],
        "experts_touched": touched,
        "expert_layer_calls": calls,
        "tile_rows": sums["tile_rows"],
        "experts_touched_per_call": round(touched / calls, 3),
        # of the experts held, summed over the same calls
        "experts_touched_share": round(touched / sums["experts_held"], 4),
        # the counts by call class: chunk calls (prefill) and
        # single-token calls (decode steps)
        "by_class": {
            "chunk": chunk,
            "single_token": {k: sums[k] - c for k, c in chunk.items()},
        },
    }


# what a RoutedExperts call sows into the ``counters`` collection, in
# the order its ``sow`` joins them
COUNTS = count_group("moe", (
    ("assignments", "Token-to-expert assignments routed"),
    ("assignments_held", "Assignments whose expert this chip holds"),
    ("experts_touched",
     "Experts a call's tokens reached, summed over calls"),
    ("expert_layer_calls",
     "Expert-layer calls (layers x steps, and chunks)"),
    ("experts_held", "Experts held, summed over calls"),
    # the first four again, over the chunk calls alone (more than one
    # token a row: prefill chunks); sum less chunk is the single-token
    # class, the decode steps
    ("chunk_assignments", "Assignments routed by chunk calls"),
    ("chunk_assignments_held", "Chunk calls' assignments held here"),
    ("chunk_experts_touched", "Experts reached, summed over chunk calls"),
    ("chunk_expert_layer_calls", "Expert-layer calls that were chunks"),
    # what the grouped matmul multiplied: tiles used x rows a tile (the
    # tile follows the call's shapes: auto_row_tile), and the chunk
    # calls' part; assignments held over it is the tiles' fill
    ("tile_rows", "Rows of the row tiles the expert layout used"),
    ("chunk_tile_rows", "Chunk calls' rows of row tiles used"),
    # the assignments that chose a zero-compute expert (an identity:
    # no weights, no row of the layout), of ``assignments``
    ("zero_assignments",
     "Assignments to zero-compute (identity) experts: no expert's "
     "weights read, no row multiplied"),
    ("chunk_zero_assignments",
     "Chunk calls' assignments to zero-compute experts"),
), block=_counts_block)


class RoutedExperts(nn.Module):
    """Dropless top-k routing over gated experts (``gate``: ``"silu"``,
    SwiGLU, or ``"relu"``, ReGLU), of which this chip may hold a share
    (expert parallelism's one-chip half), plus an optional shared
    expert of the same form on every token.

    The float32 router scores all ``n_experts`` (``router_score``:
    ``"softmax"`` over the experts, or ``"sigmoid"``, each expert's
    own); a token's top ``k`` are renormalised to sum 1 (not with
    ``renormalise`` false: the weights are then the scores as they
    are) and scaled by ``routed_scale``.  With ``selection_bias`` a
    learned number an expert (``router_bias``) is added to the scores
    for the CHOICE of the top ``k`` alone: the weights are the scores
    themselves.  ``zero_experts`` more outputs of the router, after the
    ``n_experts`` real ones, are zero-compute experts: identities with
    no weights.  A token that chooses one gets ``weight x its input``
    from it, whole on every chip of an expert-parallel layer (as a
    shared expert's part is), and the choice takes no row of the
    layout and reads nothing: the compute a token varies.
    ``experts_held = (first, count)`` says which experts' weights are
    here (None: all).  The assignments whose expert is held are sorted
    by expert (``group_layout``'s counting sort), run through the
    grouped matmul against the stacked weights ``experts_gate`` /
    ``experts_up`` (count, d, f) and ``experts_down`` (count, f, d), and
    summed back into their tokens by gate weight.  What the experts not
    held would add is left out: the result is this chip's part, and on
    one chip nothing stands in for the exchange.  No capacity, no
    dropped token, one algorithm for every token count.

    ``router_input`` (same shape as ``x``) is what the router scores
    where that is not ``x``: a layer whose router sits before its
    attention hands the attention's normed input here and the
    post-attention normed state as ``x``.

    The layout's row tile follows the call's own static shapes
    (``auto_row_tile``: the rows an expert can expect, tokens x ``k``
    over ``n_experts``): a decode step's few rows an expert sit in
    16-row tiles, a 2,048-token chunk's in 64- or 128-row ones.  A
    row's product does not depend on its tile.

    Each call sows ``[assignments made, assignments held, experts
    touched, 1, experts held]`` into the ``counters`` collection, and
    after them the first four again if the call is a chunk (more than
    one token a row) and zeros if it is a single-token step: the sums
    stay what they were, and sum minus chunk is the single-token class
    (summed where a caller makes the collection mutable; the decode
    engine does).  Then the rows of the tiles the layout used (tiles x
    rows a tile: what the kernel multiplied, pad rows included;
    assignments held over it is the tiles' fill), and that again if the
    call is a chunk; and last the assignments that chose a zero-compute
    expert, and those again if the call is a chunk.
    """

    n_experts: int
    d_model: int
    d_ff: int
    k: int
    experts_held: Optional[Tuple[int, int]] = None
    routed_scale: float = 1.0
    shared_width: int = 0
    dtype: jnp.dtype = jnp.bfloat16
    gate: str = "silu"
    router_score: str = "softmax"
    selection_bias: bool = False
    zero_experts: int = 0
    renormalise: bool = True

    @nn.compact
    def __call__(self, x, router_input=None):
        from mlcomp_tpu.ops.pallas.grouped_matmul import (
            GATES,
            auto_row_tile,
            group_layout,
            grouped_matmul,
        )

        b, s, d = x.shape
        t = b * s
        first, count = self.experts_held or (0, self.n_experts)
        routed = self.n_experts + self.zero_experts   # the router's width
        tokens = x.reshape(t, d)
        stack = lambda name, shape: self.param(  # noqa: E731
            name, nn.initializers.normal(0.02), shape, jnp.float32
        ).astype(self.dtype)
        w_gate = stack("experts_gate", (count, d, self.d_ff))
        w_up = stack("experts_up", (count, d, self.d_ff))
        w_down = stack("experts_down", (count, self.d_ff, d))

        with jax.named_scope("moe.route"):
            scored = tokens if router_input is None \
                else router_input.reshape(t, d)
            logits = nn.Dense(
                routed, use_bias=False, dtype=jnp.float32, name="router",
            )(scored.astype(jnp.float32))
            scores = ROUTER_SCORES[self.router_score](logits)
            if self.selection_bias:
                bias = self.param(
                    "router_bias", nn.initializers.zeros, (routed,),
                    jnp.float32,
                )
                _, topi = jax.lax.top_k(scores + bias, self.k)
                topv = jnp.take_along_axis(scores, topi, axis=-1)
            else:
                topv, topi = jax.lax.top_k(scores, self.k)
            gates = topv / jnp.sum(topv, axis=-1, keepdims=True) \
                if self.renormalise else topv
            gates = gates * self.routed_scale                     # (T, k)
            # a zero-compute expert's index lies past every held one: it
            # gets no row below, like an expert another chip holds
            local = (topi - first).reshape(t * self.k)
            # the rows an expert can expect are the same on every chip
            # of an expert-parallel layer: the router's published width
            tm = auto_row_tile(t, self.k, routed)
            lay = group_layout(
                local, count, tm,
                source=jnp.arange(t * self.k, dtype=jnp.int32) // self.k,
            )
            held = lay.dest < lay.row_source.shape[0]
            counts = jnp.stack([
                jnp.float32(t * self.k),
                jnp.sum(held).astype(jnp.float32),
                jnp.sum(lay.sizes > 0).astype(jnp.float32),
                jnp.float32(1.0),
                jnp.float32(count),
            ])
            tile_rows = (lay.tiles_used * tm).astype(jnp.float32)
            to_zero = topi >= self.n_experts                      # (T, k)
            zeros = jnp.sum(to_zero).astype(jnp.float32).reshape(1) \
                if self.zero_experts else jnp.zeros((1,), jnp.float32)
            mine = jnp.concatenate([counts[:4], tile_rows, zeros])
            as_chunk = mine if s > 1 else jnp.zeros_like(mine)
            self.sow(
                "counters", COUNTS.name,
                jnp.concatenate([
                    counts, as_chunk[:4], tile_rows, as_chunk[4:5],
                    zeros, as_chunk[5:],
                ]),
                reduce_fn=lambda a, c: a + c,
                init_fn=lambda: jnp.zeros(len(COUNTS.entries), jnp.float32),
            )

        with jax.named_scope("moe.experts"):
            rows = jnp.take(tokens.astype(self.dtype), lay.row_source, axis=0)
            act = grouped_matmul(
                rows, w_gate, lay.tile_group, lay.tiles_used, w2=w_up,
                gate=self.gate,
            )
            out = grouped_matmul(
                act, w_down, lay.tile_group, lay.tiles_used
            )
            # each token's sum over its held assignments, by gate weight
            # (a row no tile wrote is whatever was there: select, never
            # multiply by zero)
            picked = jnp.where(
                held[:, None],
                jnp.take(out, jnp.where(held, lay.dest, 0), axis=0), 0,
            ).reshape(t, self.k, d)
            y = jnp.einsum(
                "tkd,tk->td", picked.astype(jnp.float32), gates
            )

        if self.zero_experts:
            with jax.named_scope("moe.zero"):
                y = y + jnp.sum(
                    jnp.where(to_zero, gates, 0.0), axis=-1, keepdims=True
                ) * tokens.astype(jnp.float32)

        if self.shared_width:
            with jax.named_scope("moe.shared"):
                dense = lambda n, name: nn.Dense(  # noqa: E731
                    n, use_bias=False, dtype=self.dtype, name=name
                )
                h = tokens.astype(self.dtype)
                h = GATES[self.gate](
                    dense(self.shared_width, "shared_gate")(h)
                ) * dense(self.shared_width, "shared_up")(h)
                y = y + dense(d, "shared_down")(h).astype(jnp.float32)
        return y.astype(self.dtype).reshape(b, s, d)


class MoELayer(nn.Module):
    """Decoder layer whose FFN is a routed MoE block."""

    hidden: int
    heads: int
    kv_heads: int
    n_experts: int
    d_ff: int
    k: int
    capacity_factor: float
    dtype: jnp.dtype
    seq_parallel: "bool | str" = False
    kv_quant: bool = False

    @nn.compact
    def __call__(
        self, x, positions, train: bool = False, decode: bool = False,
        kv_mask=None, cache_cursor=None,
    ):
        from mlcomp_tpu.models.transformer import SelfAttention

        x = SelfAttention(
            self.hidden, self.heads, self.kv_heads, self.dtype,
            seq_parallel=self.seq_parallel, kv_quant=self.kv_quant,
            name="attn",
        )(x, positions, decode=decode, kv_mask=kv_mask,
          cache_cursor=cache_cursor)
        h = RMSNorm(self.dtype)(x)
        return x + MoEBlock(
            n_experts=self.n_experts,
            d_model=self.hidden,
            d_ff=self.d_ff,
            k=self.k,
            capacity_factor=self.capacity_factor,
            dtype=self.dtype,
            name="moe",
        )(h, train=train)


@MODELS.register("moe_lm")
class MoELM(nn.Module):
    """Decoder LM with MoE FFN every ``moe_every`` layers."""

    vocab_size: int = 32000
    hidden: int = 512
    layers: int = 8
    heads: int = 8
    kv_heads: Optional[int] = None
    n_experts: int = 8
    d_ff: Optional[int] = None
    k: int = 2
    capacity_factor: float = 1.25
    moe_every: int = 2
    dtype: str = "bfloat16"
    seq_parallel: "bool | str" = False
    # int8 KV cache for decode (transformer.SelfAttention.kv_quant)
    kv_quant: bool = False

    @nn.compact
    def __call__(
        self,
        x,
        train: bool = False,
        decode: bool = False,
        positions=None,
        kv_mask=None,
        cache_cursor=None,
        last_logits_only: bool = False,
    ):
        """``decode=True`` runs incremental decoding against the "cache"
        collection (see models/generation.py); the MoE FFN is stateless
        per token, so only the attention layers carry cache state.
        ``cache_cursor`` (B,) selects per-row write offsets (the
        continuous-batching engine's contract, transformer.py).
        ``last_logits_only`` (static): final norm and head on
        ``h[:, -1:]`` alone, (B, 1, vocab) out
        (``TransformerLM.__call__``)."""
        from mlcomp_tpu.models.transformer import resolve_positions

        dtype = jnp.dtype(self.dtype)
        ids = x.astype(jnp.int32)
        positions = resolve_positions(ids, decode, positions)
        kv_heads = self.kv_heads or self.heads
        d_ff = self.d_ff or self.hidden * 4

        h = nn.Embed(self.vocab_size, self.hidden, dtype=dtype, name="emb")(ids)
        for i in range(self.layers):
            if (i + 1) % self.moe_every == 0:
                h = MoELayer(
                    self.hidden, self.heads, kv_heads, self.n_experts, d_ff,
                    self.k, self.capacity_factor, dtype,
                    seq_parallel=self.seq_parallel, kv_quant=self.kv_quant,
                )(h, positions, train=train, decode=decode, kv_mask=kv_mask,
                  cache_cursor=cache_cursor)
            else:
                h = DecoderLayer(
                    self.hidden, self.heads, kv_heads, d_ff, dtype,
                    seq_parallel=self.seq_parallel, kv_quant=self.kv_quant,
                )(h, positions, decode=decode, kv_mask=kv_mask,
                  cache_cursor=cache_cursor)
        if last_logits_only:
            h = h[:, -1:]
        h = RMSNorm(dtype)(h)
        return nn.Dense(self.vocab_size, use_bias=False, dtype=jnp.float32,
                        name="lm_head")(h)
