"""decode engine: how many iterations of the drive loop an admission
holds the one admission lane for (``admission_boundaries.<x>``), mean
over the window's admissions: the ``boundaries`` argument of a request's
``inserted`` instant, the iterations from its ``admit`` to its
``inserted``, first and last included.  The lane runs one chunk an
iteration, so a cold prompt reads its chunk count; times the length of a
boundary it is ``admission_p90_ms``'s mean.  A program whose ``inserted``
says no such thing gives no number."""

from benchmark.layer_metrics.lane_wait_p90_ms import window_args


def read(name, ctx):
    mine = window_args(ctx, "inserted", "boundaries", "admission_boundaries")
    return sum(mine) / len(mine) if mine else None
