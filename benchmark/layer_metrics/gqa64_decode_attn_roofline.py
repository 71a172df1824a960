"""kernels: the int8-KV decode attention's share of its roofline at a
model whose heads are narrower than a lane tile, the bytes counted at
the PUBLISHED head width (``rooflines/gqa64_decode_attn.py``); live
tokens from the requests' own clocks, as ``kv8_decode_attn_roofline``
takes them.  None without a trace, or for an architecture whose
``dims_of`` names no ``kv_heads`` and ``head_dim``."""

from benchmark import cells, xplane
from benchmark.layer_metrics.kv8_decode_attn_roofline import live_tokens


def read(name, ctx):
    tr, (lo, hi) = ctx["trace"], ctx["slice"]
    if tr is None or ctx["peaks"] is None or lo is None:
        return None
    cfg = ctx["cell"].config
    dims = cells.architecture(cfg).dims_of(cfg)
    if not all(k in dims for k in ("heads", "kv_heads", "head_dim")):
        return None
    ctx = {**ctx, "gqa_dims": dims,
           "kv_live_tokens": live_tokens(ctx["window"]["reqs"], lo, hi)}
    return xplane.roofline_share(
        tr, cells.roofline("gqa64_decode_attn"), ctx["peaks"], ctx
    )
