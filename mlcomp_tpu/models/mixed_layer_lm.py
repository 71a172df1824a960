"""Decoder LM whose layers are not alike: built from per-layer lists.

``transformer_lm`` and ``moe_lm`` build every layer from one set of
numbers.  Here each layer names its attention kind (``"full"`` or
``"sliding"``: its own RoPE description, and a window for the sliding
kind), its number of query heads, and its MLP kind (``"dense"``
SwiGLU, or ``"sparse"``: dropless top-k routed experts plus a
shared expert, ``models/moe.py`` ``RoutedExperts``).  An attention
layer is ``transformer.SelfAttention`` (a per-head output gate, a head
width that is a field) followed by its MLP: one attention module, one
decode-attention kernel family, one cache layout (a whole ``l_buf`` a
layer; a window layer reads its last ``window`` tokens).

Four more kinds keep no keys and values (``MIXERS`` has the table):
``"retention"`` is ``models/retention.py`` ``PowerRetention``
(``rope_full``'s rotation, ``qk_norm``) and ``"kda"`` is
``models/kda.py`` ``KimiDeltaAttention`` (``conv_taps``), each with a
recurrent state of fixed size a slot as its cache; ``"conv"`` is
``models/short_conv.py`` ``GatedShortConv`` (``conv_taps``), whose
cache is its last ``conv_taps - 1`` inputs; ``"latent"`` is
``models/latent_attention.py`` ``LatentAttention`` (``latent_dims``;
``rope_full``, where the model gives one, rotates its shared key and
each head's ``q_pe``, and none rotates nothing; ``latent_q_rank`` makes
its query low-rank; ``latent_lora_scales`` turns on the two scales that
go with the low ranks), whose cache is one latent a token for all
heads.  The MLP is the same for every kind.  A stack may mix
``"full"`` with ``"sliding"``, ``"kda"`` (a state) with ``"latent"`` (a token axis), and ``"full"``
with ``"conv"`` (keys and values, int8 under ``kv_quant``, beside a
tail of fixed size): one slot's carry then holds both (the four groups
are ``SERVED_TOGETHER``).  ``"retention"`` is served alone.  With
``qk_norm`` an attention layer (``"full"``, ``"sliding"``) norms its q
and k a head before the rotation, as a retention layer does.

A third MLP kind, ``"shortcut"``, makes the layer a shortcut-connected
expert layer (``MLP_KINDS`` has the table): TWO mixers and TWO dense
MLPs a layer, and one block of routed experts that reads the first
MLP's normed input and whose output joins the residual stream after
the second MLP, a whole mixer and MLP later (``MixedLayer`` writes the
order out).  Both mixers' caches are leaves of the one layer's carry
(``attn`` and ``attn_1``), and ``attention_windows()`` counts a mixer,
not a layer.  Its mixers are latent attention (``SHORTCUT_MIXERS``).

What a model may also say: a RoPE description whose ``rotary_dim`` is
0 rotates nothing (a layer without positional embedding);
``early_router`` feeds a sparse layer's router the attention's own
normed input, the experts still the post-attention normed state;
``expert_gate`` names the experts' gate activation (``"silu"`` or
``"relu"``); ``zero_experts`` widens the router by as many zero-compute
(identity) experts and ``renormalise`` false leaves a token's chosen
weights as the router scored them (``models/moe.py``).

Serving only: the expert layer has no capacity and no auxiliary loss.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from mlcomp_tpu.models import MODELS
from mlcomp_tpu.models.kda import KimiDeltaAttention
from mlcomp_tpu.models.latent_attention import LatentAttention
from mlcomp_tpu.models.moe import ROUTER_SCORES, RoutedExperts
from mlcomp_tpu.models.retention import PowerRetention
from mlcomp_tpu.models.transformer import (
    RMSNorm,
    RopeSpec,
    SelfAttention,
    _LMHead,
    resolve_positions,
)


def _attention(layer: "MixedLayer", name: str) -> nn.Module:
    return SelfAttention(
        layer.hidden, layer.heads, layer.kv_heads, layer.dtype,
        kv_quant=layer.kv_quant, head_dim=layer.head_dim, rope=layer.rope,
        window=layer.window, head_gate=layer.head_gate,
        return_normed=layer.early_router, qk_norm=layer.qk_norm,
        name=name,
    )


def _retention(layer: "MixedLayer", name: str) -> nn.Module:
    return PowerRetention(
        layer.hidden, layer.heads, layer.kv_heads, layer.head_dim,
        layer.dtype, rope=layer.rope, qk_norm=layer.qk_norm, name=name,
    )


def _kda(layer: "MixedLayer", name: str) -> nn.Module:
    return KimiDeltaAttention(
        layer.hidden, layer.heads, layer.head_dim, layer.dtype,
        conv=layer.conv_taps, name=name,
    )


def _latent(layer: "MixedLayer", name: str) -> nn.Module:
    by_q, by_kv = layer.latent_lora_scales
    rank, latent = layer.latent_q_rank, layer.latent_dims[3]
    return LatentAttention(
        layer.hidden, layer.heads, layer.dtype, *layer.latent_dims,
        rope=layer.rope, q_rank=rank,
        q_scale=(layer.hidden / rank) ** 0.5 if by_q else 1.0,
        kv_scale=(layer.hidden / latent) ** 0.5 if by_kv else 1.0,
        name=name,
    )


def _conv(layer: "MixedLayer", name: str) -> nn.Module:
    # imported where a stack first has the kind: the stacks without it
    # load what they loaded
    from mlcomp_tpu.models.short_conv import GatedShortConv

    return GatedShortConv(
        layer.hidden, layer.dtype, taps=layer.conv_taps, name=name,
    )


# a layer kind's mixer under a name, all with ``SelfAttention``'s call
# signature
MIXERS = {
    "full": _attention, "sliding": _attention, "retention": _retention,
    "kda": _kda, "latent": _latent, "conv": _conv,
}
# the kinds that are ``SelfAttention``: keys and values a token
ATTENTION_KINDS = ("full", "sliding")
# the kinds whose cache is a state of fixed size (a recurrent state, a
# convolution's tail): they read no context tokens
STATE_KINDS = ("retention", "kda", "conv")
# an MLP kind's mixers a layer: ``"dense"`` and ``"sparse"`` follow one
# mixer; ``"shortcut"`` is mixer, dense MLP, mixer, dense MLP, with the
# routed experts beside the first three
MLP_KINDS = {"dense": 1, "sparse": 1, "shortcut": 2}
# the mixers a shortcut layer has been served with (two caches of one
# kind a layer: no other kind has a test of that)
SHORTCUT_MIXERS = ("latent",)
# the kinds one stack may hold together
SERVED_TOGETHER = (
    ("full", "sliding"), ("retention",), ("kda", "latent"), ("full", "conv"),
)
# what only ``SelfAttention`` has, and why each other kind refuses it.
# ``kv_quant`` has no ``"conv"`` row: beside attention layers it means
# their keys and values alone (the convolution's tail stays as it is),
# and a stack without any is refused below
ATTENTION_ONLY = {
    "kv_quant": {
        "retention": "there are no keys and values to quantize",
        "kda": "there are no keys and values to quantize",
        "latent": "the latent is kept as it is, not as int8",
    },
    "window": {
        "retention": "its gates do the forgetting",
        "kda": "its decays do the forgetting",
        "latent": "it reads the whole context",
        "conv": "it reads its last taps and nothing else",
    },
    "head_gate": {
        "retention": "its output has no gate",
        "kda": "its output gate is its own, a number a channel",
        "latent": "its output has no gate",
        "conv": "its output gate is its own, a number a channel",
    },
    "early_router": dict.fromkeys(
        ("retention", "kda", "latent", "conv"), "it hands no normed input on"
    ),
}


class MixedLayer(nn.Module):
    """One decoder layer: the mixer of its kind, then its MLP
    (``mlp``: ``"dense"``, ``"sparse"``), or with ``"shortcut"``, ``N``
    the layer's own norms (each mixer norms its input itself):

        x1 = x  + mixer(x)              h = N0(x1)
        m  = experts(h)                 # kept aside: the shortcut
        x2 = x1 + dense(h)
        x3 = x2 + mixer_1(x2)
        x4 = x3 + dense_1(N1(x3))
        out = x4 + m
    """

    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    dtype: jnp.dtype
    rope: Optional[RopeSpec] = None
    window: Optional[int] = None
    head_gate: bool = False
    kv_quant: bool = False
    # the MLP's kind (``MLP_KINDS``) and the dense MLPs' width
    mlp: str = "dense"
    mlp_dim: int = 0
    experts: int = 0
    experts_per_token: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    routed_scale: float = 1.0
    expert_width: int = 0
    shared_width: int = 0
    # the router scores the attention's normed input, not the experts'
    early_router: bool = False
    expert_gate: str = "silu"
    router_score: str = "softmax"
    selection_bias: bool = False
    zero_experts: int = 0
    renormalise: bool = True
    # the mixer's kind (``MIXERS``) and what only some kinds read
    kind: str = "full"
    qk_norm: bool = False
    conv_taps: int = 4
    latent_dims: Tuple[int, int, int, int] = (128, 64, 128, 512)
    latent_q_rank: Optional[int] = None
    latent_lora_scales: Tuple[bool, bool] = (False, False)

    @nn.compact
    def __call__(self, x, positions, decode=False, kv_mask=None,
                 cache_cursor=None):
        def mixer(x, name):
            return MIXERS[self.kind](self, name)(
                x, positions, decode=decode, kv_mask=kv_mask,
                cache_cursor=cache_cursor,
            )

        def dense_mlp(h, suffix=""):
            dense = lambda n, name: nn.Dense(  # noqa: E731
                n, use_bias=False, dtype=self.dtype, name=name + suffix
            )
            h = nn.silu(dense(self.mlp_dim, "gate")(h)) * dense(
                self.mlp_dim, "up"
            )(h)
            return dense(self.hidden, "down")(h)

        def experts(h, router_input=None):
            return RoutedExperts(
                n_experts=self.experts, d_model=self.hidden,
                d_ff=self.expert_width, k=self.experts_per_token,
                experts_held=self.experts_held,
                routed_scale=self.routed_scale,
                shared_width=self.shared_width, dtype=self.dtype,
                gate=self.expert_gate, router_score=self.router_score,
                selection_bias=self.selection_bias,
                zero_experts=self.zero_experts,
                renormalise=self.renormalise, name="moe",
            )(h, router_input=router_input)

        x = mixer(x, "attn")
        x, pre = x if self.early_router else (x, None)
        h = RMSNorm(self.dtype)(x)
        if self.mlp == "dense":
            return x + dense_mlp(h)
        if self.mlp == "sparse":
            return x + experts(h, pre)
        with jax.named_scope("scmoe.experts"):
            aside = experts(h)
        with jax.named_scope("scmoe.dense"):
            x = x + dense_mlp(h)
        x = mixer(x, "attn_1")
        with jax.named_scope("scmoe.dense"):
            x = x + dense_mlp(RMSNorm(self.dtype)(x), "_1")
        return x + aside


class MixedLayerLM(nn.Module):
    vocab_size: int
    hidden: int
    head_dim: int
    kv_heads: int
    layer_types: Tuple[str, ...]
    heads_per_layer: Tuple[int, ...]
    mlp_layer_types: Tuple[str, ...]
    # the dense layers' width (a model without a dense layer says none)
    mlp_dim: int = 0
    rope_full: Optional[RopeSpec] = None
    rope_sliding: Optional[RopeSpec] = None
    window: Optional[int] = None
    head_gate: bool = False
    experts: int = 0
    experts_per_token: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    routed_scale: float = 1.0
    expert_width: int = 0
    shared_width: int = 0
    early_router: bool = False
    expert_gate: str = "silu"
    # how the router scores (``moe.ROUTER_SCORES``), and whether a
    # learned bias an expert joins the scores for the choice alone
    router_score: str = "softmax"
    selection_bias: bool = False
    # zero-compute (identity) experts after the ``experts`` real ones in
    # the router's width, and whether a token's chosen weights are
    # renormalised to sum 1 (``moe.RoutedExperts``)
    zero_experts: int = 0
    renormalise: bool = True
    # a retention layer's and an attention layer's q and k are
    # RMS-normed a head before RoPE
    qk_norm: bool = False
    # the taps of a KDA layer's convolution on q, k and v, and of a
    # conv layer's own; a latent layer's widths: a head's unrotated and
    # shared key parts, its value, the latent's rank; its query's rank
    # (None: one projection) and whether the query and the normed latent
    # are scaled by (hidden / their rank) ^ 1/2
    conv_taps: int = 4
    latent_dims: Tuple[int, int, int, int] = (128, 64, 128, 512)
    latent_q_rank: Optional[int] = None
    latent_lora_scales: Tuple[bool, bool] = (False, False)
    dtype: str = "bfloat16"
    kv_quant: bool = False
    # the head's matmul operands (accumulation and logits stay float32):
    # "bfloat16" reads a bfloat16 head as it is stored
    head_dtype: str = "float32"

    def attention_windows(self) -> Tuple[Optional[int], ...]:
        """The window of each MIXER that reads context tokens (None:
        the whole context, a ``"latent"`` layer's too), for the
        engine's count of the context tokens attention reads; a layer
        that reads a state of fixed size (``STATE_KINDS``: retention,
        KDA, a convolution's tail) has no entry, so a stack of
        ``"full"`` beside ``"conv"`` counts its attention layers
        alone, and a ``"shortcut"`` layer has two (``MLP_KINDS``)."""
        return tuple(
            self.window if kind == "sliding" else None
            for kind, mlp in zip(self.layer_types, self.mlp_layer_types)
            if kind not in STATE_KINDS
            for _ in range(MLP_KINDS[mlp])
        )

    @nn.compact
    def __call__(self, x, train: bool = False, decode: bool = False,
                 positions=None, kv_mask=None, cache_cursor=None,
                 last_logits_only: bool = False):
        """``last_logits_only`` (static): the caller keeps the last
        position's logits alone, so the final norm and the head run on
        ``h[:, -1:]`` and return (B, 1, vocab); the layers, their caches
        and their counters see the whole sequence
        (``TransformerLM.__call__``)."""
        if train:
            raise NotImplementedError(
                "mixed_layer_lm is served, not trained: its expert layer "
                "has no capacity and no balance loss"
            )
        dtype = jnp.dtype(self.dtype)
        ids = x.astype(jnp.int32)
        positions = resolve_positions(ids, decode, positions)
        h = nn.Embed(self.vocab_size, self.hidden, dtype=dtype, name="emb")(ids)
        for i, (kind, heads, mlp) in enumerate(zip(
            self.layer_types, self.heads_per_layer, self.mlp_layer_types
        )):
            sliding = kind == "sliding"
            h = MixedLayer(
                self.hidden, heads, self.kv_heads, self.head_dim, dtype,
                rope=self.rope_sliding if sliding else self.rope_full,
                window=self.window if sliding else None,
                head_gate=self.head_gate, kv_quant=self.kv_quant,
                mlp=mlp, mlp_dim=self.mlp_dim,
                experts=self.experts,
                experts_per_token=self.experts_per_token,
                experts_held=self.experts_held,
                routed_scale=self.routed_scale,
                expert_width=self.expert_width,
                shared_width=self.shared_width,
                early_router=self.early_router,
                expert_gate=self.expert_gate,
                router_score=self.router_score,
                selection_bias=self.selection_bias,
                zero_experts=self.zero_experts,
                renormalise=self.renormalise,
                kind=kind, qk_norm=self.qk_norm,
                conv_taps=self.conv_taps, latent_dims=self.latent_dims,
                latent_q_rank=self.latent_q_rank,
                latent_lora_scales=self.latent_lora_scales,
                name=f"layer_{i}",
            )(h, positions, decode, kv_mask, cache_cursor)
        if last_logits_only:
            h = h[:, -1:]
        h = RMSNorm(dtype)(h)
        return _LMHead(
            self.vocab_size, self.hidden, compute_dtype=self.head_dtype,
            name="lm_head",
        )(h)


@MODELS.register("mixed_layer_lm")
def mixed_layer_lm(**cfg: Any) -> MixedLayerLM:
    """``MixedLayerLM`` from a configuration's mapping: JSON lists
    become tuples and the two RoPE mappings ``RopeSpec``s (a flax
    module's fields are hashed)."""
    lists = ("layer_types", "heads_per_layer", "mlp_layer_types")
    n = {len(cfg[k]) for k in lists}
    if len(n) != 1:
        raise ValueError(f"{lists} must be one entry a layer, got lengths {n}")
    for kind, allowed in (("layer_types", tuple(MIXERS)),
                          ("mlp_layer_types", tuple(MLP_KINDS))):
        bad = sorted(set(cfg[kind]) - set(allowed))
        if bad:
            raise ValueError(f"{kind}: {bad} not among {allowed}")
    kinds = set(cfg["layer_types"])
    if not any(kinds <= set(group) for group in SERVED_TOGETHER):
        raise ValueError(
            f"layer_types {list(cfg['layer_types'])}: one stack holds "
            f"kinds of one of {SERVED_TOGETHER}; no other mix is served yet"
        )
    for key, whys in ATTENTION_ONLY.items():
        for kind in sorted(kinds & set(whys)):
            if cfg.get(key):
                raise ValueError(
                    f"{key} on a {kind} layer: {whys[kind]} ({key} is the "
                    f"attention layers', {ATTENTION_KINDS}); layer_types "
                    f"{list(cfg['layer_types'])}"
                )
    shortcut = sorted({
        kind for kind, mlp in zip(cfg["layer_types"], cfg["mlp_layer_types"])
        if mlp == "shortcut" and kind not in SHORTCUT_MIXERS
    })
    if shortcut:
        raise ValueError(
            f"a shortcut layer of {shortcut} mixers: its two mixers keep "
            f"two caches of one kind in one layer's carry, which is served "
            f"with {SHORTCUT_MIXERS} alone; layer_types "
            f"{list(cfg['layer_types'])}, mlp_layer_types "
            f"{list(cfg['mlp_layer_types'])}"
        )
    if cfg.get("kv_quant") and not kinds & set(ATTENTION_KINDS):
        raise ValueError(
            "kv_quant: the attention layers' keys and values "
            f"{ATTENTION_KINDS} are what it quantizes, and this stack has "
            f"none; layer_types {list(cfg['layer_types'])}"
        )
    if cfg.get("router_score", "softmax") not in ROUTER_SCORES:
        raise ValueError(
            f"router_score {cfg['router_score']!r}: the router scores by "
            f"one of {sorted(ROUTER_SCORES)}"
        )
    if cfg.get("qk_norm") and not kinds & {"retention", *ATTENTION_KINDS}:
        raise ValueError(
            "qk_norm: only a retention layer and an attention layer "
            f"{ATTENTION_KINDS} norm their q and k a head, and this stack "
            f"has neither; layer_types {list(cfg['layer_types'])}"
        )
    scales = tuple(bool(v) for v in cfg.get("latent_lora_scales") or ())
    if scales and scales[0] and not cfg.get("latent_q_rank"):
        raise ValueError(
            "latent_lora_scales: the query's scale is (hidden / "
            "latent_q_rank) ^ 1/2, and the model gives no latent_q_rank"
        )
    if cfg.get("early_router") and "dense" in cfg["mlp_layer_types"]:
        raise ValueError(
            "early_router: a dense MLP has no router to move before the "
            f"attention; mlp_layer_types {list(cfg['mlp_layer_types'])}"
        )
    from mlcomp_tpu.ops.pallas.grouped_matmul import GATES

    if cfg.get("expert_gate", "silu") not in GATES:
        raise ValueError(
            f"expert_gate {cfg['expert_gate']!r}: the grouped matmul's "
            f"gates are {sorted(GATES)}"
        )
    for k in lists:
        cfg[k] = tuple(cfg[k])
    for k in ("experts_held", "latent_dims"):
        if cfg.get(k) is not None:
            cfg[k] = tuple(int(v) for v in cfg[k])
    if scales:
        cfg["latent_lora_scales"] = scales
    for k in ("rope_full", "rope_sliding"):
        cfg[k] = RopeSpec.of(cfg.get(k))
    return MixedLayerLM(**cfg)
