"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell is one entry of ``workloads``: a configuration under a traffic
mix.  Everything that belongs to one configuration, one mix or one
per-layer metric sits in a file of its own, so a later PR adds a cell by
adding files and ``BENCHMARK.json`` entries and edits nothing here:

- ``benchmark/configs/<config>.json``       sizes, service or trainer
- ``benchmark/workloads/<traffic>.json``    the mix's parameters + limits
- ``benchmark/layer_metrics/<metric>.py``   one reader: ctx -> number
- ``benchmark/rooflines/<kernel>.py``       operations and bytes from shapes
- ``benchmark/reference/<arch>.py``         an architecture: its plain
  forward pass, its seeded weights and the program's layout of them,
  named by the configuration's ``reference`` key

A rehearsal swaps in ``_rehearsal/`` siblings of the first two (tiny
widths, CPU only) and changes nothing else.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def _read(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark_spec() -> Dict[str, Any]:
    return _read(ROOT / "BENCHMARK.json")


class Cell:
    def __init__(self, name: str, rehearsal: bool = False):
        spec = benchmark_spec()
        rows = [w for w in spec["workloads"] if w["name"] == name]
        if not rows:
            known = sorted(w["name"] for w in spec["workloads"])
            raise SystemExit(f"unknown workload {name!r}; known: {known}")
        self.spec = spec
        self.name = name
        self.row = rows[0]
        self.chips = int(self.row["chips"])
        self.rehearsal = bool(rehearsal)
        cfg_row = next(
            c for c in spec["configs"] if c["name"] == self.row["config"]
        )
        cfg_path = ROOT / cfg_row["file"]
        mix_path = HERE / "workloads" / f"{self.row['traffic']}.json"
        if rehearsal:
            cfg_path = cfg_path.parent / "_rehearsal" / cfg_path.name
            mix_path = mix_path.parent / "_rehearsal" / mix_path.name
        self.config = _read(cfg_path)
        self.traffic = _read(mix_path)

    def _mine(self, metric: Dict[str, Any]) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self) -> List[Dict[str, Any]]:
        return [m for m in self.spec["end_to_end"] if self._mine(m)]

    def per_layer(self) -> List[Dict[str, Any]]:
        e2e = {m["name"] for m in self.end_to_end()}
        return [
            m for m in self.spec["per_layer"]
            if self._mine(m) and m["moves"] in e2e
        ]


def _load_py(path: Path):
    spec = importlib.util.spec_from_file_location(
        "benchmark_plugin_" + path.stem.replace(".", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layer_reader(metric: str):
    """``layer_metrics/<metric>.py``, else the file named by the part
    before the first dot (``dispatch_gap_ms.steady`` and ``.offline`` are
    one quantity read in two cells)."""
    for stem in (metric, metric.split(".", 1)[0]):
        path = HERE / "layer_metrics" / f"{stem}.py"
        if path.exists():
            return _load_py(path).read
    return None


def roofline(kernel: str):
    return _load_py(HERE / "rooflines" / f"{kernel}.py")


_ARCHITECTURES: Dict[str, Any] = {}


def architecture(cfg: Dict[str, Any]):
    """``benchmark/reference/<cfg["reference"]>.py``: the architecture a
    configuration runs — plain forward pass, seeded weights, and how the
    program lays those weights out.  Loaded once: its jitted functions
    keep their caches."""
    name = cfg.get("reference")
    if not name:
        raise SystemExit(
            "the configuration names no architecture: add "
            '"reference": "<file under benchmark/reference/>"'
        )
    if name not in _ARCHITECTURES:
        path = HERE / "reference" / f"{name}.py"
        if not path.exists():
            raise SystemExit(f"no architecture {name!r} at {path}")
        _ARCHITECTURES[name] = _load_py(path)
    return _ARCHITECTURES[name]


def kind_runner(kind: str):
    """``benchmark/kinds/<kind>.py``: one general runner per traffic kind."""
    path = HERE / "kinds" / f"{kind}.py"
    if not path.exists():
        raise SystemExit(f"no runner for traffic kind {kind!r} at {path}")
    return _load_py(path).run
