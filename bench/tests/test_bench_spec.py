"""``BENCHMARK.json`` and the files it names: shape, names, and lookup."""

from __future__ import annotations

import json
import re

import pytest

from bench import spec
from bench.tests import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(spec.SpecError, match="not in"):
        spec.peaks("TPU v9 imaginary")


def test_v5e_peaks_and_their_source():
    p = spec.peaks("TPU v5 lite")
    assert p["int8_ops_per_s"] == 393e12 and p["hbm_bytes_per_s"] == 819e9
    table = json.loads((spec.BENCH_DIR / "peaks.json").read_text())
    assert "TPU v5e" in table["source"]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"][1].startswith("bench/")
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((spec.ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024


def test_names_units_and_entries():
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for section, want in keys.items():
        names = [e["name"] for e in BENCH[section]]
        assert len(names) == len(set(names))
        for e in BENCH[section]:
            assert set(e) - {"workloads"} == want, e["name"]
            assert NAME.match(e["name"])
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in (
                    "lower", "higher")
    for e in BENCH["end_to_end"]:
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25


def test_every_cell_reports_what_it_must():
    configs = {c["name"] for c in BENCH["configs"]}
    assert {w["config"] for w in BENCH["workloads"]} == configs
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25 and "workloads" not in \
        e2e["setup_s"]
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and NAME.match(w["traffic"])
        cs = spec.load_cell(w["name"])
        names = {m["name"] for m in cs["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert cs["per_layer"]
        for m in cs["per_layer"]:
            assert m["moves"] in names
            assert m["name"] in spec.readers(cs["per_layer"])
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


def test_config_files_lie_under_the_benchmark():
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/configs/")
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])


@pytest.mark.parametrize("cell", CELLS)
def test_cells_load_by_name(cell):
    cs = spec.load_cell(cell)
    assert cs["cell"]["name"] == cell
    assert cs["traffic"]["batch"] >= 1


def test_unknown_cell_is_an_error():
    with pytest.raises(spec.SpecError, match="no workload"):
        spec.load_cell("no.such-cell")


def test_every_config_and_mix_has_cpu_sizes():
    """The tests run every cell at the sizes in ``bench/tests/sizes``."""
    missing = tiny.missing()
    assert not missing, "no CPU sizes: " + ", ".join(map(str, missing))


def test_every_kind_keeps_the_contract():
    for c in BENCH["configs"]:
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        mod = spec.kind(cfg["kind"])
        assert all(n.startswith("bench.") for n in mod.SPANS)
        for name in ("set_up", "window", "metric", "release", "check",
                     "control"):
            assert callable(getattr(mod, name)), (cfg["kind"], name)
