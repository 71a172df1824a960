"""decode engine: of the slot rows a dispatch carries, the share that
held a request when it went out (``live_rows_share.<x>``): batch size a
step over the batch the program was built for.  The engine splits every
dispatch's rows three ways at ``issue``, from its host mirror
(``stats()["engine"]["attention"]``): ``rows_attended``, ``rows_starved``
(empty while a request waited for one) and the unasked rest of
``rows_total``.  Read as ``stats1 - stats0``, so the ramp at the window's
opening and the drain after its close are in it.  A program that does
not keep the whole ledger (no ``rows_starved``) gives no number: the two
shares are read together or not at all.  ``rows(ctx)`` is shared with
``starved_rows_share``."""

KEYS = ("rows_attended", "rows_starved", "rows_total")


def rows(ctx):
    """{key: stats1 - stats0} of the engine's row ledger, or None where
    the program keeps none or no dispatch was issued in between."""
    after = ((ctx.get("stats1") or {}).get("engine") or {}).get("attention") or {}
    before = ((ctx.get("stats0") or {}).get("engine") or {}).get("attention") or {}
    if any(k not in after for k in KEYS):
        return None
    out = {k: after[k] - before.get(k, 0) for k in KEYS}
    return out if out["rows_total"] > 0 else None


def read(name, ctx):
    got = rows(ctx)
    return None if got is None else (
        100.0 * got["rows_attended"] / got["rows_total"])
