"""``chip_smoke.py``: refuses to run off the chip; its checks hold on CPU.

The script itself only passes on a TPU.  Here it must fail -- with no
result line -- under the CPU backend and outside a checkout; and its
phase functions, run at a tiny size in interpret mode with the
compiled-mode assertion lifted, must accept the stack's real answers
(so a wrong oracle or expectation is caught before it costs chip time).
"""

from __future__ import annotations

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def run_script(cwd: Path, tmp_path: Path, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    return subprocess.run([sys.executable, str(cwd / "chip_smoke.py"),
                           *args], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def assert_no_result(proc):
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_cpu_backend_exits_nonzero(tmp_path):
    proc = run_script(ROOT, tmp_path)
    assert_no_result(proc)
    assert "no TPU found" in proc.stderr


def test_script_alone_exits_nonzero(tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(SCRIPT, alone / "chip_smoke.py")
    assert_no_result(run_script(alone, tmp_path))


@pytest.fixture
def smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "assert_compiled", lambda *engines: None)
    return mod


def test_one_chip_phases_pass_on_cpu(smoke, capsys):
    smoke.run_one_chip(smoke.Phases(), n_rows=4096, mxu_fallback_rows=256)
    out = capsys.readouterr().out
    assert "planted hits all found" in out
    assert "plan mxu: backend=mxu" in out


def test_four_chip_phase_passes_on_cpu_devices(smoke, capsys):
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices (forced host devices)")
    phases = smoke.Phases()
    smoke.run_four_chips(phases, n_rows=4096, chunk_rows=1024)
    out = capsys.readouterr().out
    assert "stage best_after_compact: sharded == unsharded" in out
    assert "on 4 devices" in out
    # Growth, tombstones and compaction within the reserved chunk keep
    # every scan's shape: no kernel launch is compiled again.
    grown = phases.by_phase["append_rows + tombstone + compact"]
    assert not [p for p in grown if "swar" in p or "filter" in p], grown


def test_wrong_answer_fails_the_smoke(smoke):
    """The checks bite: a result that disagrees with the oracle fails."""
    import numpy as np

    from repro.match import MatchEngine, MatchQuery

    rng = np.random.default_rng(0)
    frags = rng.integers(0, 4, (64, smoke.F), np.uint8)
    res = MatchEngine(frags).match(MatchQuery.exact(frags[5, :smoke.P]))
    smoke.check_best_on_sample("ok", res, frags, np.arange(64),
                               smoke.exact_masks(frags[5, :smoke.P]))
    res.best_scores = res.best_scores.copy()
    res.best_scores[7] += 1
    with pytest.raises(smoke.SmokeFailure, match="differs"):
        smoke.check_best_on_sample("bad", res, frags, np.arange(64),
                                   smoke.exact_masks(frags[5, :smoke.P]))
