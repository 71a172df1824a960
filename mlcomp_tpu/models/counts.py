"""What a layer counts, written once: a table beside the layer.

A layer that sows a float32 vector into the ``counters`` collection
declares a ``CountGroup`` under the name it sows: the vector's entries
in order, each with the help text of its metric
(``mlcomp_engine_<group>_<entry>_total``), and, where ``stats()``
reports more than the sums, the function that makes the group's block.
The serving engine learns a group from the name of the sown leaf and
finds its table here; tools/graftcheck.py reads the same declarations
with ``ast``, so the name and the entries are written as literals.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

Sums = Dict[str, float]
# (the group's sums by entry, rows the host issued x the group's
# layers) -> the group's block of stats(), None to leave it out
Block = Callable[[Sums, float], Optional[Dict[str, Any]]]


def sums_block(sums: Sums, issued: float):
    """The block of a group that reports its sums and nothing else."""
    return dict(sums)


@dataclasses.dataclass(frozen=True)
class CountGroup:
    name: str
    entries: Tuple[Tuple[str, str], ...]   # (entry, help), vector order
    block: Block = sums_block

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(name for name, _ in self.entries)


# in the order declared, which is the order an engine joins the groups
# of one model in: the tail of its packed buffer, stats(), /metrics
_GROUPS: Dict[str, CountGroup] = {}


def count_group(name: str, entries, block: Block = sums_block
                ) -> CountGroup:
    """Declare the table of the group sown under ``name``."""
    group = CountGroup(name, tuple((n, h) for n, h in entries), block)
    known = _GROUPS.get(name)
    if known is not None and known.entries != group.entries:
        raise ValueError(
            f"counters group {name!r} is already declared with other "
            f"entries ({known.names}); sow under another name"
        )
    _GROUPS[name] = group
    return group


def count_groups(sown: Iterable[str]) -> List[CountGroup]:
    """The tables of the groups sown under the names ``sown``, in the
    order declared."""
    sown = set(sown)
    unknown = sorted(sown - set(_GROUPS))
    if unknown:
        raise ValueError(
            f"a layer sows counters under {unknown} and no table "
            "declares them: declare one beside the layer's sow with "
            "mlcomp_tpu.models.counts.count_group(name, ((entry, help), "
            "...))"
        )
    return [group for name, group in _GROUPS.items() if name in sown]


def state_rows_block(sums: Sums, issued: float):
    """The block of a per-slot state kind (``state_rows``,
    ``state_bytes``, ``chunk_tokens``, ``layer_calls``): the sums, and
    the device's count of rows over the host mirror's (rows holding a
    request at issue x steps x layers): under 1 by the rows that
    retired inside a dispatch."""
    if not sums["layer_calls"]:
        return None
    return {
        **sums,
        "state_rows_over_issued": round(
            sums["state_rows"] / issued, 4
        ) if issued else None,
    }
