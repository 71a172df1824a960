"""expert layer: of the experts held, the share a call's tokens reached
(the weights a call has to read), over the run."""

from benchmark.layer_metrics.moe_counts import delta


def read(name, ctx):
    moe = delta(ctx)
    if moe is None:
        return None
    held = float(ctx["cell"].config["num_experts"])
    return 100.0 * moe["experts_touched"] / (moe["expert_layer_calls"] * held)
