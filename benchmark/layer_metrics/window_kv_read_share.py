"""model step: of the context tokens the decode attention reads (the
part of the live KV inside each layer's window, summed over live rows,
attention layers and dispatches), the share read by the layers that
have a window, over the run.  From the engine's counts by layer kind
(``stats()["engine"]["attention"]["by_kind"]``); None where the program
does not split its counts."""


def read(name, ctx):
    after = ((ctx.get("stats1") or {}).get("engine") or {}).get("attention") or {}
    before = ((ctx.get("stats0") or {}).get("engine") or {}).get("attention") or {}
    if "by_kind" not in after:
        return None

    def seen(kind):
        was = (before.get("by_kind") or {}).get(kind) or {}
        return (after["by_kind"][kind]["kv_tokens_attended"]
                - was.get("kv_tokens_attended", 0))

    window, full = seen("window"), seen("full")
    return 100.0 * window / (window + full) if window + full > 0 else None
