"""What the expert layer counted over the run (``stats()["engine"]["moe"]``, after
less before); None where the program counts no such thing."""

KEYS = ("assignments", "assignments_held", "experts_touched",
        "expert_layer_calls")


def delta(ctx):
    """{key: stats1 - stats0}, or None without calls to count."""
    after = ((ctx.get("stats1") or {}).get("engine") or {}).get("moe")
    if not after:
        return None
    before = ((ctx.get("stats0") or {}).get("engine") or {}).get("moe") or {}
    out = {k: float(after[k]) - float(before.get(k, 0.0)) for k in KEYS}
    return out if out["expert_layer_calls"] > 0 else None
