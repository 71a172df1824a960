"""conv layer: bytes of per-slot cache the single-token steps read for
each token the service emitted, over the run, in MB: the keys and values
the attention layers attended, AS STORED, plus the conv layers' tails.

- keys and values: ``stats()["engine"]["attention"]["kv_tokens_attended"]``
  (context tokens the live rows held when a dispatch was issued, summed
  over attention layers and dispatches) x K, the steps a dispatch runs
  (each reads the row's context; the up to K - 1 tokens a row grows by
  inside a dispatch are not counted), x the bytes a token a layer of the
  int8 cache's own leaves (``cached_key_q`` / ``cached_value_q`` and
  their scales, from their SHAPES: a head of 64 stored in 128 lanes is
  128 B a token, which is where packing two heads a tile would show);
- tails: ``stats()["engine"]["conv"]["state_bytes"]``, each live row's
  tail read and written once a layer a step.

``stats1 - stats0``.  None where the program counts no conv layer (a
parent without the kind, a model without it) or keeps no int8 keys and
values."""

KV_LEAVES = ("cached_key_q", "cached_value_q", "cached_key_scale",
             "cached_value_scale")


def stored_bytes_a_token_a_layer(model_cfg):
    """Bytes one context token takes in one attention layer's cache
    leaves, pad lanes included, from the leaves' shapes; None for a
    model without the int8 leaves."""
    import jax
    import jax.numpy as jnp

    from mlcomp_tpu.models import create_model

    model = create_model(dict(model_cfg))
    ids = jnp.zeros((1, 128), jnp.int32)
    cache = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), ids, decode=True, positions=ids))["cache"]
    total, layers = 0.0, 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        name = getattr(path[-1], "key", str(path[-1]))
        if name not in KV_LEAVES:
            continue
        # (1, kv heads, buffer, lanes) int8, (1, kv heads, 1, buffer)
        # scales: the buffer is the one axis both share
        buffer = leaf.shape[2] if name.endswith("_q") else leaf.shape[3]
        total += leaf.size * leaf.dtype.itemsize / buffer
        layers += name == "cached_key_q"
    return total / layers if layers else None


def moved(ctx):
    """(bytes of keys and values read, bytes of tails moved, tokens
    emitted) over the run, or None."""
    after = (ctx.get("stats1") or {}).get("engine") or {}
    if not after.get("conv") or "kv_tokens_attended" not in (
            after.get("attention") or {}):
        return None
    before = (ctx.get("stats0") or {}).get("engine") or {}

    def delta(group, key):
        return float(after[group][key]) - float(
            (before.get(group) or {}).get(key, 0.0))

    cfg = ctx["cell"].config
    a_token = stored_bytes_a_token_a_layer(cfg["model"])
    tokens = float(after["emitted_tokens"]) - float(
        before.get("emitted_tokens", 0))
    k = cfg["service"].get("steps_per_dispatch")
    if a_token is None or tokens <= 0 or not isinstance(k, int):
        return None
    kv = delta("attention", "kv_tokens_attended") * k * a_token
    return kv, delta("conv", "state_bytes"), tokens


def read(name, ctx):
    got = moved(ctx)
    if got is None:
        return None
    kv, tails, tokens = got
    return (kv + tails) / tokens / 1e6
