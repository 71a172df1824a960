"""kernels: the grouped matmul's share of its roofline over the traced
slice, every call costed by its own class (prefill chunk or
single-token step): rows and experts touched a call from the program's
counters by call class (``stats()["engine"]["moe"]["by_class"]``), the
class from the op's own shapes (``rooflines/grouped_matmul_by_class.py``).
None where the program does not count by class."""

from benchmark import cells, xplane
from benchmark.layer_metrics.moe_counts import KEYS


def classes(ctx):
    """{class: {key: stats1 - stats0}}, or None without such counts."""
    after = ((ctx.get("stats1") or {}).get("engine") or {}).get("moe") or {}
    before = ((ctx.get("stats0") or {}).get("engine") or {}).get("moe") or {}
    if "by_class" not in after:
        return None
    out = {
        name: {k: float(c[k])
               - float(((before.get("by_class") or {}).get(name) or {})
                       .get(k, 0.0)) for k in KEYS}
        for name, c in after["by_class"].items()
    }
    return out if any(c["expert_layer_calls"] > 0 for c in out.values()) \
        else None


def read(name, ctx):
    by_class = classes(ctx)
    if ctx["trace"] is None or ctx["peaks"] is None or by_class is None:
        return None
    return xplane.roofline_share(
        ctx["trace"], cells.roofline("grouped_matmul_by_class"),
        ctx["peaks"], {**ctx, "moe_classes": by_class},
    )
