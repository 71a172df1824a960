"""model step: of the context tokens the live rows held, summed over
attention layers and dispatches, the share inside each layer's window
(what the decode attention reads), over the run."""


def read(name, ctx):
    after = ((ctx.get("stats1") or {}).get("engine") or {}).get("attention") or {}
    before = ((ctx.get("stats0") or {}).get("engine") or {}).get("attention") or {}
    if "kv_tokens_live" not in after:
        return None
    live = after["kv_tokens_live"] - before.get("kv_tokens_live", 0)
    seen = after["kv_tokens_attended"] - before.get("kv_tokens_attended", 0)
    return 100.0 * seen / live if live > 0 else None
