"""Autoregressive generation: KV-cache decode loop + sampling.

The upstream reference has no generative path (its infer stage is a batch
forward pass); this module is part of the LLM-era surface the TPU build
adds, alongside the long-context machinery.  TPU-first design:

- ONE compiled step for the whole decode loop: the KV cache is a fixed
  ``(B, prompt + budget)`` buffer (allocated via ``jax.eval_shape`` — no
  throwaway init forward), every step updates it in place at
  ``cache_index`` and attends under a slot mask, so shapes are static and
  `lax.scan` drives the loop on device — zero host round-trips per token;
- prefill and decode share the same code path (the cache write and mask
  handle any incoming length), so the prompt is absorbed in one batched
  MXU-friendly pass, not token by token;
- ragged prompts batch via LEFT-padding: ``prompt_mask`` drives per-row
  RoPE positions and masks pad slots out of attention.

``generate`` is a pure function of (variables, prompt, rng) — wrap it in
``jax.jit`` with the model/knob args static for production use (the test
suite does exactly that).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp


def decode_shapes(model, batch_size: int, max_len: int) -> Dict[str, Any]:
    """The shapes of every collection the model makes under
    ``decode=True`` for ``(batch_size, max_len)`` (``cache``, and
    ``counters`` where its layers sow): ``jax.eval_shape`` over
    ``model.init``, so no forward pass runs and nothing is allocated."""
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((batch_size, max_len), jnp.int32),
            decode=True,
            positions=jnp.zeros((batch_size, max_len), jnp.int32),
        )
    )
    if "cache" not in shapes:
        raise ValueError(
            f"{type(model).__name__} creates no 'cache' collection under "
            "decode=True; generation needs a decode-capable model"
        )
    return shapes


def init_cache(model, batch_size: int, max_len: int) -> Dict[str, Any]:
    """Allocate a zeroed decode cache for ``(batch_size, max_len)``:
    the cache pytree's structure from :func:`decode_shapes`, then
    zeros."""
    shapes = decode_shapes(model, batch_size, max_len)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes["cache"])


def process_logits(
    logits: jax.Array,
    temperature: float,
    top_k: Optional[int],
    top_p: Optional[float],
) -> jax.Array:
    """Temperature/top-k/top-p filtering over (B, V) next-token logits.

    ``top_p >= 1`` and ``top_k >= V`` are no-ops; ``top_p <= 0`` and
    ``top_k <= 0`` are config errors (they would mask every token).
    """
    logits = logits.astype(jnp.float32) / max(temperature, 1e-6)
    if top_k is not None:
        if top_k <= 0:
            raise ValueError(f"top_k must be positive, got {top_k}")
        k = min(top_k, logits.shape[-1])
        kth = jax.lax.top_k(logits, k)[0][:, -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None:
        if top_p <= 0.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if top_p < 1.0:
            sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
            probs = jax.nn.softmax(sorted_logits, axis=-1)
            # keep the smallest prefix whose mass reaches top_p (the first
            # token always survives: its exclusive-prefix mass is 0)
            keep = (jnp.cumsum(probs, axis=-1) - probs) < top_p
            cutoff = jnp.min(
                jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True
            )
            logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


def sample_token(
    rng: jax.Array,
    logits: jax.Array,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
) -> jax.Array:
    """Draw next tokens (B,) from (B, V) logits; temperature 0 = greedy."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        rng, process_logits(logits, temperature, top_k, top_p)
    ).astype(jnp.int32)


def process_logits_rowwise(
    logits: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    top_p: jax.Array,
) -> jax.Array:
    """Per-ROW sampling filters: knobs are traced (B,) arrays, so one
    compiled program serves every knob combination (the serving path —
    static knobs would multiply the compile cache by every distinct
    temperature a client sends).

    Neutral values are well-defined per row: ``top_k >= V`` and
    ``top_p >= 1`` keep everything; ``temperature`` is clamped (greedy
    rows are selected OUTSIDE, in ``sample_token_rowwise``, where the
    argmax needs the unfiltered logits anyway).  ``top_k`` uses a rank
    mask (argsort-of-argsort) rather than ``lax.top_k`` because k is
    data here, not a static shape.
    """
    v = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    logits = logits / jnp.maximum(temperature[:, None], 1e-6)
    # ONE descending sort serves both filters (this runs per decode
    # token on the serving hot path): the per-row k-th VALUE gathers
    # from it (same keep-ties-with-the-kth semantics as the static
    # lax.top_k path), and top-p reads its k-filtered prefix masses
    sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(
        sorted_logits, jnp.clip(top_k, 1, v)[:, None] - 1, axis=-1
    )
    sl_k = jnp.where(sorted_logits < kth, -jnp.inf, sorted_logits)
    probs = jax.nn.softmax(sl_k, axis=-1)
    keep = (jnp.cumsum(probs, axis=-1) - probs) < top_p[:, None]
    cutoff = jnp.min(
        jnp.where(keep, sl_k, jnp.inf), axis=-1, keepdims=True
    )
    logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jnp.where(logits < cutoff, -jnp.inf, logits)


def sample_token_rowwise(
    rng: jax.Array,
    logits: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    top_p: jax.Array,
) -> jax.Array:
    """Per-row knobs version of ``sample_token``: rows with
    ``temperature <= 0`` decode greedily, the rest sample through the
    row-wise filters — all inside one traced program.  An all-greedy
    batch (the common default) skips the sort/softmax/categorical work
    entirely via ``lax.cond`` at runtime, so the zero-recompile
    property costs nothing when nobody samples."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sampled_branch():
        sampled = jax.random.categorical(
            rng, process_logits_rowwise(logits, temperature, top_k, top_p)
        ).astype(jnp.int32)
        return jnp.where(temperature <= 0.0, greedy, sampled)

    return jax.lax.cond(
        jnp.any(temperature > 0.0), sampled_branch, lambda: greedy
    )


def sample_token_rowwise_keyed(
    keys: jax.Array,
    logits: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    top_p: jax.Array,
) -> jax.Array:
    """:func:`sample_token_rowwise` with PER-ROW keys (``keys``:
    (rows, 2) uint32): row r draws its token from its OWN key instead
    of sharing one batch key.  The continuous engine derives row r's
    key as ``fold_in(fold_in(engine_rng, request_seed), position)``,
    so a request's sampled stream depends only on (engine seed,
    request, token index) — NEVER on which dispatch carried the step,
    how deep the pipeline ran, or when neighbours joined.  That
    per-request stream is what makes emitted tokens bit-identical
    under any adaptive-K schedule; the greedy fast path is unchanged."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sampled_branch():
        proc = process_logits_rowwise(logits, temperature, top_k, top_p)
        sampled = jax.vmap(jax.random.categorical)(keys, proc).astype(
            jnp.int32
        )
        return jnp.where(temperature <= 0.0, greedy, sampled)

    return jax.lax.cond(
        jnp.any(temperature > 0.0), sampled_branch, lambda: greedy
    )


def prep_decode_variables(model, variables, quant_kernel, weights_dtype):
    """Decode-loop weight prep for ``generate``: int8 entry-dequant or kernel-fold (with the
    optimization barrier that pins ONE materialized copy outside the
    token loop), optional bf16 pre-cast, and the apply wrapper that
    routes quantized leaves through the Pallas interception (with norm
    folding for models that declare ``fold_norms_eligible``).

    Returns ``(variables, apply_model)`` — ``apply_model`` closes over
    the interception choice, ``variables`` over the prep.  The measured
    trade-offs live in the comments below.
    """
    from mlcomp_tpu.ops.quant import dequantize_params, has_quantized

    # Decode reads every weight once per token, so weight bytes bound the
    # step time.  Two int8 modes:
    # - default (storage): dequantize ONCE at entry, decode runs bf16.
    #   In-scan jnp dequant was measured SLOWER than bf16 (XLA
    #   materializes the dequantized copy per token).
    # - ``quant_kernel=True``: keep kernel-consumable leaves int8 and
    #   route their Dense/DenseGeneral/Embed ops through the Pallas int8
    #   matmul (ops/pallas/quant_matmul.py) — the dequant happens in
    #   VMEM, so those weights cost HALF the HBM read per token.  Since
    #   round 3 this includes the 3-D attention projections (folded to
    #   2-D; quantize_params puts their scales on the true contraction
    #   axes), so ~100% of decoder weight bytes stay int8.
    # Measured (v5e, 268M LM, 128 new tokens, interleaved medians,
    # ms/tok): B=4 bf16 1.74 / entry 1.63 / kernel 1.61; B=8 bf16 1.68 /
    # entry 1.60 / kernel 1.72.  The kernel wins only in the weight-
    # bound middle (B≈4): at B=1 Pallas per-call overhead dominates
    # (bf16 wins) and at B≥8 weights amortize over rows so entry-dequant
    # bf16 edges ahead.  Deltas are within ~5% of session noise — treat
    # the mode as a knob to A/B on the target batch, not a universal win.
    # The OTHER big decode stream — the KV cache, dominant at B≥8 — is
    # the model's ``kv_quant`` flag (int8 cache + Pallas flash-decode,
    # ops/pallas/decode_attention.py): measured 1.44× end-to-end at
    # B=8/1.2B/S=2304, composable with every weight mode here.
    use_quant_kernel = False
    if has_quantized(variables):
        from mlcomp_tpu.ops.quant import dequantize_nonkernel_params

        use_quant_kernel = bool(quant_kernel)
        deq = dequantize_nonkernel_params if quant_kernel else dequantize_params
        # without the barrier XLA re-runs the (cheap-looking) dequant
        # inside every scan iteration, re-reading the int8 AND writing
        # bf16 per token — the barrier pins one materialized copy
        prepped = deq(
            variables,
            weights_dtype if weights_dtype is not None else jnp.bfloat16,
        )
        if use_quant_kernel:
            # pre-shape the kernel operands once, outside the token loop
            # (a 3-D leaf reshaped per call measured as a 12 MB in-loop
            # relayout copy — see fold_kernel_leaves)
            from mlcomp_tpu.ops.quant import fold_kernel_leaves

            prepped = fold_kernel_leaves(prepped)
        variables = jax.lax.optimization_barrier(prepped)
    elif weights_dtype is not None:
        # same eligibility rule as quantize_params: only big matrices.
        # 1D leaves (RMSNorm scales — fp32 by design) and small tensors
        # keep their dtype, so norm math and tiny heads are untouched;
        # note large fp32-compute kernels (lm_head) DO get cast — that
        # precision trade is why this is opt-in, not default.
        variables = jax.tree.map(
            lambda x: x.astype(weights_dtype)
            if (
                hasattr(x, "ndim") and x.ndim >= 2 and x.size >= 4096
                and jnp.issubdtype(x.dtype, jnp.floating)
            )
            else x,
            variables,
        )
        variables = jax.lax.optimization_barrier(variables)

    def apply_model(*args, **kwargs):
        if use_quant_kernel:
            from mlcomp_tpu.ops.quant import quant_kernel_interception

            # fold RMSNorms into the consuming projection kernels on
            # decode-GEMV shapes (models that declare every norm
            # consumer dense-like; see quant_kernel_interception)
            with quant_kernel_interception(
                fold_norms=bool(
                    getattr(model, "fold_norms_eligible", False)
                )
            ):
                return model.apply(*args, **kwargs)
        return model.apply(*args, **kwargs)

    return variables, apply_model


def generate(
    model,
    variables: Dict[str, Any],
    prompt: jax.Array,
    max_new_tokens: int,
    *,
    prompt_mask: Optional[jax.Array] = None,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    eos_id: Optional[int] = None,
    pad_id: int = 0,
    rng: Optional[jax.Array] = None,
    weights_dtype=None,
    quant_kernel: bool = False,
    with_logprobs: bool = False,
    repetition_penalty: Optional[jax.Array] = None,
):
    """Generate ``max_new_tokens`` continuations of ``prompt`` (B, S).

    - ``variables``: the model's non-cache variables ({"params": ...});
      may carry int8 weight-only quantized leaves from
      ``ops.quant.quantize_params`` — dequantized once at entry (see the
      measured trade-offs below).
    - ``weights_dtype``: opt-in pre-cast of large weight matrices before
      the token loop (bf16 ≈ 1.4× decode on v5e vs fp32 masters; costs
      weight-mantissa precision on fp32-compute heads).  None (default)
      leaves dtypes untouched.
    - ``prompt_mask`` (B, S): True on real tokens, False on LEFT-padding;
      pad rows get RoPE positions counted from their first real token and
      their pad slots never attend.
    - ``eos_id``: rows emit ``pad_id`` after producing ``eos_id``.
    - sampling knobs: floats/ints trace STATICALLY (distinct values =
      distinct programs; the simple path).  Passing ``temperature`` as
      a (B,) ARRAY switches to per-ROW sampling (``top_k``/``top_p``
      arrays optional then, neutral per row when omitted): one compiled
      program serves any knob mix — what the serving daemon batches
      mixed requests with.
    - ``repetition_penalty`` (rowwise only, (B,) floats, 1.0 = off):
      tokens already seen (real prompt ids + everything generated so
      far, tracked as a (B, V) presence mask carried through the scan)
      get the HF-convention adjustment (positive logits divided,
      negative multiplied) BEFORE greedy/sampling; reported logprobs
      stay raw-model.

    Returns (B, S + max_new_tokens) int32 ids (prompt included; padding
    preserved as given).  With ``with_logprobs=True`` (static — a
    second program variant) returns ``(ids, logprobs)`` where logprobs
    is (B, max_new_tokens) f32: the RAW-model log-probability of each
    emitted token (log_softmax of the unfiltered, untempered logits —
    the serving-API convention, so values are comparable across
    sampling settings); rows already past EOS report 0.0.
    """
    prompt = prompt.astype(jnp.int32)
    b, s = prompt.shape
    if max_new_tokens <= 0:
        if with_logprobs:
            return prompt, jnp.zeros((b, 0), jnp.float32)
        return prompt
    total = s + max_new_tokens
    cache = init_cache(model, b, total)
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    fixed, apply_model = prep_decode_variables(
        model, variables, quant_kernel, weights_dtype
    )

    def model_vars(cache):
        return {**fixed, "cache": cache}

    if prompt_mask is not None:
        pm = prompt_mask.astype(jnp.bool_)
        positions = jnp.maximum(jnp.cumsum(pm, axis=1) - 1, 0).astype(jnp.int32)
        real_len = jnp.sum(pm, axis=1).astype(jnp.int32)  # (B,)
        kv_mask = jnp.concatenate(
            [pm, jnp.ones((b, max_new_tokens), jnp.bool_)], axis=1
        )
    else:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        real_len = jnp.full((b,), s, jnp.int32)
        kv_mask = None

    logits, updated = apply_model(
        model_vars(cache),
        prompt,
        decode=True,
        positions=positions,
        kv_mask=kv_mask,
        mutable=["cache"],
        last_logits_only=True,
    )
    cache = updated["cache"]
    last_logits = logits[:, -1]

    rowwise = hasattr(temperature, "ndim")
    if rowwise:
        vocab = getattr(model, "vocab_size", None) or (1 << 30)

        def row(x, dtype):
            # 0-d scalars broadcast to every row; (B,) passes through
            return jnp.broadcast_to(
                jnp.asarray(x, dtype).reshape(-1), (b,)
            )

        t_row = row(temperature, jnp.float32)
        k_row = (
            jnp.full((b,), vocab, jnp.int32) if top_k is None
            else row(top_k, jnp.int32)
        )
        p_row = (
            jnp.ones((b,), jnp.float32) if top_p is None
            else row(top_p, jnp.float32)
        )
        rp_row = (
            None if repetition_penalty is None
            else row(repetition_penalty, jnp.float32)
        )
    elif repetition_penalty is not None:
        raise ValueError(
            "repetition_penalty needs the rowwise sampling path — pass "
            "temperature as a (B,) array (see the sampling-knobs note)"
        )

    def next_token(rng, logits, done, presence=None):
        if rowwise:
            adj = logits
            if presence is not None:
                rp = rp_row[:, None]
                la = adj.astype(jnp.float32)
                adj = jnp.where(
                    presence, jnp.where(la > 0, la / rp, la * rp), la
                )
            tok = sample_token_rowwise(rng, adj, t_row, k_row, p_row)
        else:
            tok = sample_token(rng, logits, temperature, top_k, top_p)
        tok = jnp.where(done, jnp.int32(pad_id), tok)
        if with_logprobs:
            lp = jnp.take_along_axis(
                jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1),
                tok[:, None], axis=-1,
            )[:, 0]
            lp = jnp.where(done, 0.0, lp)
        else:
            lp = jnp.zeros((tok.shape[0],), jnp.float32)
        if eos_id is not None:
            done = done | (tok == eos_id)
        return tok, lp, done

    use_rp = rowwise and repetition_penalty is not None
    if use_rp:
        # (B, V) seen-token mask: real prompt ids seed it (left-pads
        # excluded via prompt_mask), each sampled token joins its row
        vocab_v = last_logits.shape[-1]
        rows = jnp.arange(b)[:, None]
        seeds = (
            pm if prompt_mask is not None
            else jnp.ones((b, s), jnp.bool_)
        )
        presence0 = jnp.zeros((b, vocab_v), jnp.bool_).at[
            rows, prompt
        ].max(seeds)
    else:
        presence0 = jnp.zeros((b, 1), jnp.bool_)  # carry placeholder

    def step(carry, _):
        cache, last_logits, done, pos, rng, presence = carry
        rng, sub = jax.random.split(rng)
        tok, lp, new_done = next_token(
            sub, last_logits, done, presence if use_rp else None
        )
        if use_rp:
            presence = presence.at[jnp.arange(b), tok].max(~done)
        logits, updated = apply_model(
            model_vars(cache),
            tok[:, None],
            decode=True,
            positions=pos[:, None],
            kv_mask=kv_mask,
            mutable=["cache"],
        )
        return (
            (updated["cache"], logits[:, -1], new_done, pos + 1, rng,
             presence),
            (tok, lp),
        )

    # N-1 scan steps (each samples, then forwards to produce the next
    # logits); the final token needs no forward pass of its own
    done0 = jnp.zeros((b,), jnp.bool_)
    (_, last_logits, done, _, rng, presence), (tokens, lps) = jax.lax.scan(
        step,
        (cache, last_logits, done0, real_len, rng, presence0),
        None,
        length=max_new_tokens - 1,
    )
    rng, sub = jax.random.split(rng)
    final, final_lp, _ = next_token(
        sub, last_logits, done, presence if use_rp else None
    )
    tokens = jnp.concatenate([tokens.T, final[:, None]], axis=1)
    ids = jnp.concatenate([prompt, tokens], axis=1)
    if with_logprobs:
        return ids, jnp.concatenate([lps.T, final_lp[:, None]], axis=1)
    return ids
