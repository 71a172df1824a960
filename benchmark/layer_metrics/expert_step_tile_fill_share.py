"""expert layer: of the rows the single-token calls' row tiles held, the
share that carried an assignment (the rest is padding the kernel
multiplied), over the run: ``expert_tile_fill_share.py``'s quantity for
the other call class, the single-token class's assignments held over
its ``tile_rows`` (``stats()["engine"]["moe"]["by_class"]
["single_token"]``, after less before).  At 224 slots of 4 experts a
token a step lays 896 assignments over 64 experts in 16-row tiles: 14
rows an expert on the mean, and every expert above 16 spills into a
second tile.  None where the program does not count the tiles, or ran
no single-token call."""


def read(name, ctx):
    def step(stats):
        moe = ((ctx.get(stats) or {}).get("engine") or {}).get("moe") or {}
        return (moe.get("by_class") or {}).get("single_token") or {}

    after, before = step("stats1"), step("stats0")
    if "tile_rows" not in after:
        return None
    held, rows = (float(after[k]) - float(before.get(k, 0.0))
                  for k in ("assignments_held", "tile_rows"))
    return 100.0 * held / rows if rows > 0 else None
