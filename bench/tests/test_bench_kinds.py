"""A new deployment joins the benchmark as files: a kind found by name, its
configuration, its mix, their CPU sizes, and entries in ``BENCHMARK.json``.

Each addition under ``bench/tests/data/add/<name>/`` holds its files at
their place in the repository, and ``entries.json``: the configurations
and workloads it adds to ``BENCHMARK.json``, and for each metric the
cells it adds to that metric's ``workloads``.  A copy of the
benchmark takes the addition; no file the copy had changes, and the new
cell runs correct on the CPU through the benchmark's own harness.

* ``probe``: a kind of its own (``bench/kinds/probe.py``) that serves
  exact lookups and one ingest through ``MatchService`` and reads the
  acknowledged rows back by lookup, checked in NumPy.
* ``readmap-v4``: a read-mapping cell that is data only: reads with 0-4
  substitutions, hits at >= 96.

Their names are the tests' own, so a real cell of the same mix can join
the benchmark beside them.
"""

from __future__ import annotations

import hashlib
import json
import shutil
from pathlib import Path

import pytest

from bench import spec
from bench.tests import tiny
from bench.tests.test_bench_faults import plant
from bench.tests.test_bench_harness import SEED, run_cell

ADD = spec.BENCH_DIR / "tests" / "data" / "add"
NEW_CELL = {"probe": "probe.b8", "readmap-v4": "readmap.probe-v4-b16"}


def copy_benchmark(dst: Path) -> Path:
    """``BENCHMARK.json`` and everything under ``bench/``, as committed."""
    dst.mkdir(parents=True)
    shutil.copy(spec.ROOT / "BENCHMARK.json", dst)
    shutil.copytree(spec.BENCH_DIR, dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return dst


def digests(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*")) if p.is_file()}


def add(root: Path, name: str, skip=()) -> None:
    """Copy the addition's files into ``root`` (none may be there yet,
    and those named in ``skip`` stay out) and its entries into
    ``BENCHMARK.json``."""
    src = ADD / name
    for f in sorted(src.rglob("*")):
        rel = f.relative_to(src)
        if not f.is_file() or str(rel) in ("entries.json", *skip):
            continue
        assert not (root / rel).exists(), rel
        (root / rel).parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(f, root / rel)
    entries = json.loads((src / "entries.json").read_text())
    bench = tiny.benchmark(root)
    bench["configs"] += entries.get("configs", [])
    bench["workloads"] += entries["workloads"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] += entries["metrics"].get(m["name"], [])
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """Per addition: the copy that took it, the digests of the copy's
    files before it, and the cut-down tree of the copy."""
    out = {}
    for name in NEW_CELL:
        base = tmp_path_factory.mktemp(name)
        repo = copy_benchmark(base / "repo")
        before = digests(repo)
        add(repo, name)
        out[name] = (repo, before, tiny.tree(base / "tiny", source=repo))
    return out


@pytest.fixture
def cpu(monkeypatch):
    from bench import run
    monkeypatch.setattr(run, "look_for_chip", tiny.cpu_chip)
    monkeypatch.setattr(run, "use_compile_cache", lambda root: "off")
    return run


def without(bench: dict, name: str) -> dict:
    """``bench`` with the addition's entries taken out again."""
    entries = json.loads((ADD / name / "entries.json").read_text())
    cells = {w["name"] for w in entries["workloads"]}
    out = json.loads(json.dumps(bench))
    out["configs"] = [c for c in out["configs"]
                      if c not in entries.get("configs", [])]
    out["workloads"] = [w for w in out["workloads"] if w["name"] not in cells]
    for m in out["end_to_end"] + out["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [w for w in m["workloads"] if w not in cells]
    return out


@pytest.mark.parametrize("name", sorted(NEW_CELL))
def test_new_cell_is_files_only(name, added, cpu, capsys):
    repo, before, root = added[name]
    after = digests(repo)
    assert {f for f in before if after.get(f) != before[f]} == {
        "BENCHMARK.json"}
    assert without(tiny.benchmark(repo), name) == tiny.benchmark()
    assert tiny.missing(repo) == []
    cell = NEW_CELL[name]
    rc, res, err = run_cell(cpu, root, cell, capsys)
    assert rc == 0 and res["correct"] is True, err
    assert set(res["metrics"]) == {"setup_s", "queries_per_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert all(v["value"] == 0 for v in res["checks"].values())
    assert err.strip().splitlines()[-1].startswith("check ")
    assert digests(repo) == after


def test_probe_wrong_answers_are_not_correct(added, cpu, capsys,
                                             monkeypatch):
    """The probe's check fails answers altered where the engine makes
    them (a made-up hit on every lookup)."""
    plant("answer", monkeypatch)
    rc, res, err = run_cell(cpu, added["probe"][2], "probe.b8", capsys)
    assert rc == 0 and res["correct"] is False, err
    assert res["checks"]["hits_wrong"]["value"] > 0
    assert res["checks"]["readback_missing"]["value"] == 0


def test_probe_control_is_not_correct(added):
    """The probe's control, reads of the corpus as it stood before the
    ingest, misses every acknowledged row."""
    import jax

    from bench import run
    cs = spec.load_cell("probe.b8", added["probe"][2])
    out = run.run_cell(cs, SEED, 1.0, False, jax.devices()[:1],
                       tiny.CPU_PEAKS, control=True)
    assert out["correct"] is True, out["checks"]
    assert out["control"]["readback_missing"]["value"] > 0
    assert run.is_correct(out["control"]) is False


@pytest.mark.parametrize("kind", ["nosuch", None])
def test_missing_kind_is_a_plain_error(kind, tmp_path, capsys):
    """A configuration whose kind has no module, or that names none, gives
    exit code 2, the file named, and no result line."""
    from bench import run
    root = tiny.tree(tmp_path)
    cell = tiny.benchmark(root)["workloads"][0]
    path = root / "bench" / "configs" / f"{cell['config']}.json"
    cfg = json.loads(path.read_text())
    if kind is None:
        del cfg["kind"]
    else:
        cfg["kind"] = kind
    path.write_text(json.dumps(cfg))
    rc, res, err = run_cell(run, root, cell["name"], capsys)
    assert rc == 2 and res is None
    want = (f"missing {root / 'bench' / 'kinds' / 'nosuch.py'}"
            if kind else f"{path} names no kind")
    assert want in err


def test_missing_sizes_file_is_named(tmp_path):
    """A mix without CPU sizes is named by ``tiny.missing``, and the
    cut-down tree leaves its cell out and keeps the others."""
    repo = copy_benchmark(tmp_path / "repo")
    skip = "bench/tests/sizes/traffic/probe-v4-b16.json"
    add(repo, "readmap-v4", skip=(skip,))
    assert tiny.missing(repo) == [repo / skip]
    cells = [w["name"] for w in tiny.benchmark(
        tiny.tree(tmp_path / "tiny", source=repo))["workloads"]]
    assert cells == tiny.CELLS and NEW_CELL["readmap-v4"] not in cells
