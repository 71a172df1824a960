"""expert layer: of the rows the chunk calls' row tiles held, the share
that carried an assignment (the rest is padding the kernel multiplied),
over the run: the chunk class's assignments held over its ``tile_rows``
(``stats()["engine"]["moe"]["by_class"]["chunk"]``, after less before).
None where the program does not count the tiles, or ran no chunk."""


def read(name, ctx):
    def chunk(stats):
        moe = ((ctx.get(stats) or {}).get("engine") or {}).get("moe") or {}
        return (moe.get("by_class") or {}).get("chunk") or {}

    after, before = chunk("stats1"), chunk("stats0")
    if "tile_rows" not in after:
        return None
    held, rows = (float(after[k]) - float(before.get(k, 0.0))
                  for k in ("assignments_held", "tile_rows"))
    return 100.0 * held / rows if rows > 0 else None
