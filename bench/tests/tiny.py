"""A copy of the benchmark's tree at sizes the CPU runs in seconds.

``tree(tmp)`` writes ``BENCHMARK.json``, the configurations and the
traffic mixes with every cell kept but its corpus and batch cut down,
links the metric readers, the peaks table and the program's sources,
and returns the new root.  Only the sizes change: the readers,
the generator, the client loop and the check are the benchmark's own.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

from bench import spec

CPU_PEAKS = {"bf16_flops_per_s": 1e12, "int8_ops_per_s": 2e12,
             "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}

CONFIG_SIZES = {
    # Reads keep their length, so the planner filters as on the chip.
    "readmap-chr1": {"genome_bp": 400000},
}

TRAFFIC_SIZES = {
    "topk-b16": {"batch": 4, "warmup_batches": 1, "check_sample": 12},
    "v2-b16": {"batch": 4, "warmup_batches": 1, "check_sample": 12,
               "warm_survivors": [8, 64, 40]},
}


def benchmark() -> dict:
    return json.loads((spec.ROOT / "BENCHMARK.json").read_text())


CELLS = [w["name"] for w in benchmark()["workloads"]]


def tree(tmp: Path, extra_cells=()) -> Path:
    """The cut-down tree under ``tmp``, with ``extra_cells`` (workload
    entries) added; puts the program on ``sys.path``."""
    real = spec.ROOT
    if str(real / "src") not in sys.path:
        sys.path.insert(0, str(real / "src"))
    bench = benchmark()
    bench["workloads"] += list(extra_cells)
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir()
    for c in bench["configs"]:
        cfg = json.loads((real / c["file"]).read_text())
        cfg.update(CONFIG_SIZES[c["name"]])
        (tmp / c["file"]).write_text(json.dumps(cfg))
    for w in bench["workloads"]:
        name = w["traffic"]
        tr = json.loads((real / "bench" / "traffic" / f"{name}.json")
                        .read_text())
        tr.update(TRAFFIC_SIZES[name])
        (tmp / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(tr))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    peaks = json.loads((real / "bench" / "peaks.json").read_text())
    peaks["devices"]["cpu"] = CPU_PEAKS
    (tmp / "bench" / "peaks.json").write_text(json.dumps(peaks))
    os.symlink(real / "bench" / "metrics", tmp / "bench" / "metrics")
    os.symlink(real / "src", tmp / "src")
    return tmp


def cpu_chip(chips, root):
    """Stands in for the look for a TPU: the CPU with its test peaks."""
    import jax
    return jax.devices()[:chips], spec.peaks("cpu", root)
