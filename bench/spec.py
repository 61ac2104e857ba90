"""What a cell is made of, found by name under ``bench/``.

``BENCHMARK.json`` names each cell's configuration and traffic mix.  The
configuration is the file its entry names (``bench/configs/<config>.json``),
the mix ``bench/traffic/<traffic>.json``, the code of the configuration's
kind of deployment ``bench/kinds/<kind>.py`` (the configuration's
``"kind"``), and a per-layer metric's reader ``bench/metrics/<metric>.py``
(or, for a metric split by a dotted suffix such as
``device_idle_share.readmap``, the reader of its stem,
``device_idle_share.py``).  Adding a cell, a configuration, a mix, a kind
or a metric adds files and entries; it edits none.

``bench/run.py`` does what every cell shares: arguments, the look for the
chip, the compile cache, ``setup_s``, the trace, peak memory, the per-layer
readers and the result line.  A kind module does the rest, through these
names:

``SPANS``
    the names of the spans its client records (``bench.*``); with the
    program's spans they name the trace's idle gaps.
``set_up(cs, seed, devices, trace) -> dep``
    the data from the seed, the deployment built on ``devices`` and warmed
    on the cell's own shapes (``cs`` is what ``load_cell`` returns).  The
    object ``dep`` holds the deployment: ``dep.stack`` is the
    ``drive.Stack`` whose service counters and spans the harness reads,
    and ``dep.note`` says what was set up, for the log.
``window(dep, seconds, trace) -> drive.Window``
    the cell's clients until the window closes by the kind's rule, once
    ``seconds`` have passed; the answers to check stay in ``dep``.
``metric(name, win) -> float``
    an end-to-end metric other than ``setup_s``; a ``SpecError`` for one
    the kind does not measure.
``release(dep) -> held``
    what the check needs once the program's state is freed: the data and
    the kept answers, nothing of the program.
``check(held, win) -> (numbers, note)``
    every compared number, ``{name: {"value": v, "limit": l}}`` in a fixed
    order, and what was checked, for the log.
``control(held, win) -> numbers``
    the same numbers with the control's answers in the program's place
    (``bench/control.py``).
"""

from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class SpecError(Exception):
    """A cell, file or device the benchmark cannot run with."""


def _load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing {path}") from None


def _module(path: Path, name: str) -> ModuleType:
    """The Python file ``path``, run as a module named ``name``."""
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[name] = mod
    mod_spec.loader.exec_module(mod)
    return mod


def kind(name: str, root: Path = ROOT) -> ModuleType:
    """The module of a kind of deployment, ``bench/kinds/<name>.py``."""
    name = str(name)
    path = root / "bench" / "kinds" / f"{name}.py"
    if not NAME.match(name) or not path.is_file():
        raise SpecError(f"missing {path}: no module for the kind "
                        f"{name!r}")
    return _module(path, f"bench_kind_{name.replace('.', '_')}")


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` with its configuration, traffic, kind and metrics.

    Returns ``{"cell", "config", "traffic", "kind", "end_to_end",
    "per_layer"}``: ``kind`` is the module of the configuration's kind, and
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
    config_file = root / configs[cell["config"]]["file"]
    config = _load_json(config_file)
    if "kind" not in config:
        raise SpecError(f"{config_file} names no kind")
    traffic = _load_json(root / "bench" / "traffic"
                         / f"{cell['traffic']}.json")

    def mine(m):
        return name in m.get("workloads", [name])
    return {"cell": cell, "config": config, "traffic": traffic,
            "kind": kind(config["kind"], root),
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
            return _module(path, f"bench_metric_{stem.replace('.', '_')}"
                           ).read
    raise SpecError(f"no reader for metric {name!r} under {base}")


def readers(metrics: List[dict], root: Path = ROOT) -> Dict[str, Callable]:
    return {m["name"]: metric_reader(m["name"], root) for m in metrics}
