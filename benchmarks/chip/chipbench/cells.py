"""Find a cell's pieces by name: configuration, traffic mix, entry, metrics.

Nothing here knows a particular cell.  A cell of ``BENCHMARK.json`` names a
configuration and a mix; the configuration is ``configs/<name>.json``, the
mix ``traffic/<name>.json``, the mix names its entry (``entries/<entry>.py``)
and each metric whose ``workloads`` list holds the cell (or that has no
such list) is read by ``metrics/<metric>.py``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


class CellError(ValueError):
    """A cell or one of its files is missing or malformed."""


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    reader: ModuleType


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    entry: ModuleType
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_module(path: Path) -> ModuleType:
    """Import one file by path (names may hold dots, as ``mfu.decode``)."""
    if not path.is_file():
        raise CellError(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod       # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> Dict:
    if not path.is_file():
        raise CellError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def _metrics(spec: Dict, cell: str, bench_dir: Path) -> List[Metric]:
    return [Metric(m["name"], m["unit"],
                   load_module(bench_dir / "metrics" / f"{m['name']}.py"))
            for m in spec if cell in m.get("workloads", [cell])]


def find_cell(name: str, root: Path = ROOT,
              bench_dir: Optional[Path] = None) -> Cell:
    """Resolve cell ``name`` of ``<root>/BENCHMARK.json`` to its files."""
    bench_dir = bench_dir or BENCH_DIR
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"no cell {name!r}; cells: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise CellError(f"cell {name} names unknown config {w['config']!r}")
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    entry = load_module(bench_dir / "entries" / f"{traffic['entry']}.py")
    return Cell(name, int(w["chips"]), config, traffic, entry,
                _metrics(bench["end_to_end"], name, bench_dir),
                _metrics(bench["per_layer"], name, bench_dir))
