"""What a cell is made of, found by name under ``bench/``.

``BENCHMARK.json`` names each cell's configuration and traffic mix.  The
configuration is ``bench/configs/<config>.json``, the mix
``bench/traffic/<traffic>.json`` and a per-layer metric's reader
``bench/metrics/<metric>.py`` (or, for a metric split by a dotted
suffix such as ``device_idle_share.readmap``, the reader of its stem,
``device_idle_share.py``).  Adding a cell, a configuration, a mix or a
metric adds files and entries; it edits none.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class SpecError(Exception):
    """A cell, file or device the benchmark cannot run with."""


def _load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing {path}") from None


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` with its configuration, traffic and metrics.

    Returns ``{"cell", "config", "traffic", "end_to_end", "per_layer"}``:
    the metrics are the ``BENCHMARK.json`` entries that this cell reports
    (an entry without ``workloads`` is reported by every cell).
    """
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; "
                        f"have {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(root / configs[cell["config"]]["file"])
    traffic = _load_json(root / "bench" / "traffic"
                         / f"{cell['traffic']}.json")

    def mine(m):
        return name in m.get("workloads", [name])
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    table = _load_json(root / "bench" / "peaks.json")
    if device_kind not in table["devices"]:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        f"bench/peaks.json ({sorted(table['devices'])})")
    return table["devices"][device_kind]


def metric_reader(name: str, root: Path = ROOT
                  ) -> Callable[[dict], Optional[float]]:
    """``read(ctx)`` of a per-layer metric, from its own file."""
    base = root / "bench" / "metrics"
    for stem in (name, name.split(".")[0]):
        path = base / f"{stem}.py"
        if path.exists():
            mod_spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(mod_spec)
            mod_spec.loader.exec_module(mod)
            return mod.read
    raise SpecError(f"no reader for metric {name!r} under {base}")


def readers(metrics: List[dict], root: Path = ROOT) -> Dict[str, Callable]:
    return {m["name"]: metric_reader(m["name"], root) for m in metrics}
