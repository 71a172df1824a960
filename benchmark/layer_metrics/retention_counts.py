"""What the retention layers counted over the run
(``stats()["engine"]["retention"]``, after less before) beside the
engine's own steps and emitted tokens; None where the program counts no
such thing (a parent without the layer, a model without one)."""

KEYS = ("state_rows", "state_bytes")
ENGINE_KEYS = ("steps", "emitted_tokens")


def delta(ctx):
    """{key: stats1 - stats0}, or None without a single-token step to
    count."""
    after = (ctx.get("stats1") or {}).get("engine") or {}
    if not after.get("retention"):
        return None
    before = (ctx.get("stats0") or {}).get("engine") or {}
    was = before.get("retention") or {}
    out = {k: float(after["retention"][k]) - float(was.get(k, 0.0))
           for k in KEYS}
    for k in ENGINE_KEYS:
        out[k] = float(after[k]) - float(before.get(k, 0))
    return out if out["state_rows"] > 0 and out["steps"] > 0 else None
