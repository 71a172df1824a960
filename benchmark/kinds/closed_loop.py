"""Closed loop: a fixed number of clients, each sending its next request
when its last one completes."""

from benchmark.serving import run_serve


def run(**kw):
    return run_serve("closed", **kw)
