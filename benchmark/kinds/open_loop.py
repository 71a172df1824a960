"""Open loop: requests are due on the mix's fixed schedule (exponential
gaps at stratified quantiles, block-balanced order; ``traffic.py``) at
the rate fixed in the mix's file, whatever the service makes of them."""

from benchmark.serving import run_serve


def run(**kw):
    return run_serve("open", **kw)
