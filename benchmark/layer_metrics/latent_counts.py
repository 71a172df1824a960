"""What the latent-attention layers counted over the run
(``stats()["engine"]["latent"]``, after less before) beside the
engine's own steps and emitted tokens, for a stack whose ONLY cache is
latents (``cache_counts.py`` asks for KDA counts beside them and reads
None here); None where the program counts no such thing."""

KEYS = ("tokens_attended", "bytes_read", "layer_calls")
ENGINE_KEYS = ("steps", "emitted_tokens")


def delta(ctx):
    """{"tokens_attended": n, "bytes_read": n, "layer_calls": n,
    "steps": n, "emitted_tokens": n} as ``stats1 - stats0``, or None
    without a single-token step to count."""
    after = (ctx.get("stats1") or {}).get("engine") or {}
    if not after.get("latent"):
        return None
    before = (ctx.get("stats0") or {}).get("engine") or {}
    out = {k: float(after["latent"][k])
           - float((before.get("latent") or {}).get(k, 0.0)) for k in KEYS}
    for k in ENGINE_KEYS:
        out[k] = float(after[k]) - float(before.get(k, 0))
    return out if out["tokens_attended"] > 0 and out["steps"] > 0 else None
