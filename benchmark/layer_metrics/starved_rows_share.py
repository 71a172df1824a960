"""decode engine: of the slot rows a dispatch carries, the share that
went out EMPTY while a request was waiting for one
(``starved_rows_share.<x>``): queued behind the admission lane or a full
house, or mid-prefill in the lane.  ``rows_starved`` over ``rows_total``
of ``stats()["engine"]["attention"]``, ``stats1 - stats0``.  With
``live_rows_share.<x>`` it sums to at most 100; the rest are rows nobody
asked for.  A program that keeps no such count gives no number."""

from benchmark.layer_metrics.live_rows_share import rows


def read(name, ctx):
    got = rows(ctx)
    return None if got is None else (
        100.0 * got["rows_starved"] / got["rows_total"])
