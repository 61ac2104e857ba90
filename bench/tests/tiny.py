"""A copy of the benchmark's tree at sizes the CPU runs in seconds.

``tree(tmp)`` writes ``BENCHMARK.json``, the configurations and the
traffic mixes with every cell kept but its corpus and batch cut down,
links the kinds, the metric readers and the program's sources, adds the
CPU to the peaks table, and returns the new root.  Only the sizes change:
the kinds' data, clients and checks and the readers are the benchmark's
own.

The CPU sizes of a configuration or a mix lie in a file of their own,
``bench/tests/sizes/configs/<config>.json`` or
``bench/tests/sizes/traffic/<mix>.json``: the keys that replace the
file's own.  A cell whose configuration or mix has none is left out of
the tree and of ``CELLS``; ``missing`` names those files.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path
from typing import List

from bench import spec

CPU_PEAKS = {"bf16_flops_per_s": 1e12, "int8_ops_per_s": 2e12,
             "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}

SIZES = Path("bench") / "tests" / "sizes"


def benchmark(source: Path = spec.ROOT) -> dict:
    return json.loads((source / "BENCHMARK.json").read_text())


def sizes_file(section: str, name: str, source: Path = spec.ROOT) -> Path:
    """The CPU sizes of a configuration (``configs``) or a mix
    (``traffic``)."""
    return source / SIZES / section / f"{name}.json"


def sizes(section: str, name: str, source: Path = spec.ROOT) -> dict:
    return json.loads(sizes_file(section, name, source).read_text())


def sized(cell: dict, source: Path = spec.ROOT) -> bool:
    return (sizes_file("configs", cell["config"], source).is_file()
            and sizes_file("traffic", cell["traffic"], source).is_file())


def missing(source: Path = spec.ROOT) -> List[Path]:
    """The sizes files that the configurations and mixes of
    ``BENCHMARK.json`` lack."""
    bench = benchmark(source)
    want = [("configs", c["name"]) for c in bench["configs"]]
    want += [("traffic", t) for t in
             sorted({w["traffic"] for w in bench["workloads"]})]
    return [p for p in (sizes_file(s, n, source) for s, n in want)
            if not p.is_file()]


CELLS = [w["name"] for w in benchmark()["workloads"] if sized(w)]


def tree(tmp: Path, extra_cells=(), source: Path = spec.ROOT) -> Path:
    """The cut-down tree of the benchmark at ``source`` under ``tmp``,
    with ``extra_cells`` (workload entries) added; puts the program on
    ``sys.path``."""
    real = spec.ROOT
    if str(real / "src") not in sys.path:
        sys.path.insert(0, str(real / "src"))
    bench = benchmark(source)
    bench["workloads"] = [w for w in bench["workloads"] + list(extra_cells)
                          if sized(w, source)]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    (tmp / "bench" / "traffic").mkdir(parents=True)
    for name in {w["config"] for w in bench["workloads"]}:
        cfg = json.loads((source / files[name]).read_text())
        cfg.update(sizes("configs", name, source))
        (tmp / files[name]).parent.mkdir(parents=True, exist_ok=True)
        (tmp / files[name]).write_text(json.dumps(cfg))
    for name in {w["traffic"] for w in bench["workloads"]}:
        tr = json.loads((source / "bench" / "traffic" / f"{name}.json")
                        .read_text())
        tr.update(sizes("traffic", name, source))
        (tmp / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(tr))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    peaks = json.loads((source / "bench" / "peaks.json").read_text())
    peaks["devices"]["cpu"] = CPU_PEAKS
    (tmp / "bench" / "peaks.json").write_text(json.dumps(peaks))
    for d in ("kinds", "metrics"):
        os.symlink(source / "bench" / d, tmp / "bench" / d)
    os.symlink(real / "src", tmp / "src")
    return tmp


def cpu_chip(chips, root):
    """Stands in for the look for a TPU: the CPU with its test peaks."""
    import jax
    return jax.devices()[:chips], spec.peaks("cpu", root)
