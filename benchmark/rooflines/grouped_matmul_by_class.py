"""``ops/pallas/grouped_matmul.py`` where the calls of a window are of
two classes that differ by two orders of magnitude: a prefill chunk
(thousands of tokens, every expert reached, bound by compute or by all
the experts' bytes) and a single-token decode step (one token a slot,
bound by the touched experts' bytes).  ``rooflines/grouped_matmul.py``
gives every call the window's MEAN rows, which costs a decode call at
a chunk's rows and a chunk call at a fraction of its own.

Here a call is costed by ITS class.  The program counts, by class,
assignments made, assignments held, experts touched and calls
(``ctx["moe_classes"]``: ``{class: {assignments, assignments_held,
experts_touched, expert_layer_calls}}`` over the run).  The op's own
shapes say which class it is: its row buffer is the layout of its
assignments (each expert's rows padded to whole tiles: ``a + min(G, a)
(tm - 1)`` rows, rounded up to a tile, for ``a`` assignments, ``G``
experts handed in and ``tm`` rows a tile, all three in the op's
shapes), and the class whose mean assignments a call lays out nearest
to the op's rows is the op's class.  Operations and bytes are then
``rooflines/grouped_matmul.py``'s, with that class's mean rows held and
mean experts touched a call."""

from benchmark import cells
from benchmark.xplane import hlo_shapes


def match(op: str) -> bool:
    return op.split(" = ")[0].startswith("%grouped_matmul")


def laid_out(assignments: float, groups: int, tm: int) -> float:
    """Rows of the tile-padded buffer that holds ``assignments`` rows
    split over ``groups`` groups in the worst case."""
    worst = assignments + min(groups, assignments) * (tm - 1)
    return -(-worst // tm) * tm


def class_of(op: str, classes):
    """The name of the class whose calls have this op's row buffer."""
    shapes = hlo_shapes(op.split(", custom_call_target")[0])
    rows = shapes[0][1][0]
    # the two int32 operands: a group a tile, and the (1,) tiles used
    tiles = max(s[1][0] for s in shapes[1:]
                if s[0] == "s32" and len(s[1]) == 1)
    groups = next(s[1][0] for s in shapes[1:] if len(s[1]) == 3)
    tm = rows // tiles
    live = {
        name: c for name, c in classes.items() if c["expert_layer_calls"] > 0
    }
    return min(live, key=lambda name: abs(laid_out(
        live[name]["assignments"] / live[name]["expert_layer_calls"],
        groups, tm) - rows))


def cost(op: str, ctx):
    classes = ctx["moe_classes"]
    mine = classes[class_of(op, classes)]
    calls = mine["expert_layer_calls"]
    return cells.roofline("grouped_matmul").cost(op, {
        **ctx,
        "moe_rows_per_call": mine["assignments_held"] / calls,
        "moe_experts_per_call": mine["experts_touched"] / calls,
    })
