"""Whole runs of every cell on the CPU, at the sizes of ``tiny.py``.

The look for a chip is replaced by the CPU; everything else is the run the
chip makes: generation, the deployment, warm-up, the window, the reference
check and the result line.  Without that replacement a run on the CPU
must refuse, and so must a tree that holds only the benchmark.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import spec
from bench.tests import tiny

CELLS = tiny.CELLS
SEED = 2 ** 31 + 77


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.tree(tmp_path_factory.mktemp("bench"))


@pytest.fixture
def cpu(monkeypatch):
    from bench import run
    monkeypatch.setattr(run, "look_for_chip", tiny.cpu_chip)
    monkeypatch.setattr(run, "use_compile_cache", lambda root: "off")
    return run


def run_cell(run, root, cell, capsys, trace=0, seconds=1.0):
    rc = run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                   str(seconds), "--trace", str(trace)], root=root)
    out = capsys.readouterr()
    lines = out.out.strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), out.err


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct(cell, tree, cpu, capsys):
    rc, res, err = run_cell(cpu, tree, cell, capsys)
    assert rc == 0 and res["correct"] is True, err
    want = {m["name"] for m in spec.load_cell(cell, tree)["end_to_end"]}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks" and res["checks"]["no_sample"] == {
        "value": 0, "limit": 0}
    assert err.strip().splitlines()[-1].startswith("check ")
    assert "plan: backend=" in err


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_per_layer_metrics(cell, tree, cpu, capsys):
    rc, res, err = run_cell(cpu, tree, cell, capsys, trace=1)
    assert rc == 0 and res["correct"] is True, err
    per_layer = spec.load_cell(cell, tree)["per_layer"]
    # The CPU trace has no TPU plane: the trace readers find nothing and
    # are left out; the counter readers answer.
    assert set(res["metrics"]) <= {m["name"] for m in per_layer}
    assert not any(m["source"] == "device_trace" and m["name"] in
                   res["metrics"] for m in per_layer)
    assert any(n.startswith("compiles_in_window") for n in res["metrics"])
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_tpu_no_result(tree, capsys, monkeypatch):
    from bench import run
    monkeypatch.setattr(run, "use_compile_cache", lambda root: "off")
    rc, res, err = run_cell(run, tree, CELLS[0], capsys)
    assert rc != 0 and res is None
    assert "no TPU" in err


def test_benchmark_alone_gives_no_result(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no repro package" in p.stderr


def test_warm_up_reaches_every_survivor_bucket(tree):
    import jax

    from bench import drive, gen
    cs = spec.load_cell("readmap.v2-b16", tree)
    config, traffic = cs["config"], cs["traffic"]
    F, stride = config["fragment_chars"], config["row_stride"]
    P, G = config["pattern_chars"], config["genome_bp"]
    g = gen.genome(SEED, G, F, stride)
    stack = drive.build_stack(config, gen.fold(g, G, F, stride),
                              jax.devices()[:1], False)
    lo, hi, tries = traffic["warm_survivors"]
    seen = cs["kind"].warm_buckets(
        stack.service, gen.read_stream(SEED, g, G, P, traffic, gen.WARMUP),
        traffic, lo, hi, tries)
    assert set(seen) >= {8, 16, 32, 64}, seen
    assert all(b // 2 < n <= b or b == 8 for b, n in seen.items())


def test_v2_window_compiles_nothing(tree, cpu, capsys):
    """Every survivor bucket the window meets was warmed in set-up."""
    rc, res, err = run_cell(cpu, tree, "readmap.v2-b16", capsys, seconds=3)
    assert rc == 0 and res["correct"] is True, err
    assert "strategy=filter" in err
    assert re.search(r"^window: .* 0 backend compiles;", err, re.M), err


FOUR_CHIPS = r"""
import json, sys
from pathlib import Path
sys.path.insert(0, sys.argv[1])
import jax
from bench import drive, run
from bench.tests import tiny
root = tiny.tree(Path(sys.argv[2]), [{
    "name": "readmap4x.topk-b16", "config": "readmap-chr1",
    "traffic": "topk-b16", "chips": 4, "why": "four-chip test"}])
run.look_for_chip = tiny.cpu_chip
run.use_compile_cache = lambda root: "off"
stack = drive.build_stack(json.loads(
    (root / "bench/configs/readmap-chr1.json").read_text()),
    __import__("numpy").zeros((67, 128), "uint8"), jax.devices()[:4], False)
form = stack.engine.corpus.swar_words(0)
print("shards", stack.engine.n_shards,
      len({s.device for s in form.addressable_shards}))
sys.exit(run.main(["--workload", "readmap4x.topk-b16", "--seed", "5",
                   "--seconds", "1", "--trace", "0"], root=root))
"""


def test_four_chip_cell_shards_the_corpus(tmp_path):
    """A cell that asks for four chips runs its corpus over four devices,
    from its ``BENCHMARK.json`` entry alone."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        "--xla_force_host_platform_device_count=4"))
    p = subprocess.run(
        [sys.executable, "-c", FOUR_CHIPS, str(spec.ROOT), str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    assert lines[0] == "shards 4 4"
    res = json.loads(lines[-1])
    assert res["correct"] is True and res["device"]["count"] == 4
    assert "on 4 shard(s)" in p.stderr
