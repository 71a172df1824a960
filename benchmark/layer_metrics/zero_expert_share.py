"""expert layer: of the assignments the router made (all of a token's
choices), the share that chose a zero-compute expert, over the run, in
percent: the compute a token that costs no expert's weights and no row
(``stats()["engine"]["moe"]["zero_assignments"]`` over
``["assignments"]``, after less before).  None where the program does
not count them (a parent without zero experts)."""


def read(name, ctx):
    after = ((ctx.get("stats1") or {}).get("engine") or {}).get("moe") or {}
    if "zero_assignments" not in after:
        return None
    before = ((ctx.get("stats0") or {}).get("engine") or {}).get("moe") or {}
    zero, made = (float(after[k]) - float(before.get(k, 0.0))
                  for k in ("zero_assignments", "assignments"))
    return 100.0 * zero / made if made > 0 else None
