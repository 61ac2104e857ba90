"""The check fails what it must: each fault a cell can have, planted in
the timed path on the CPU, and the control in the program's place.

Faults (one run each, at the sizes of ``tiny.py``):

* ``answer``: the engine alters an answer where it produces it (a top-k
  score one higher, a threshold hit added);
* ``half``: the service runs half of a coalesced group and hands the
  other half answers of the first.

A cell on one chip has no exchange between chips to leave out, and a
read-only cell no state that a step could leave unchanged.

The control is the reference computed with float8 scores, checked as if
the timed path had produced it (``bench/control.py`` runs it on the chip).
"""

from __future__ import annotations

import itertools

import numpy as np
import pytest

from bench import spec
from bench.tests import tiny
from bench.tests.test_bench_harness import SEED, run_cell

CELLS = tiny.CELLS
FAULTS = [(c, f) for c in CELLS for f in ("answer", "half")]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.tree(tmp_path_factory.mktemp("bench"))


def plant(fault, monkeypatch):
    from repro.match import engine, service

    if fault == "answer":
        run = engine.CompiledMatch.run

        def altered(self):
            res = run(self)
            if res.topk_scores is not None:
                res.topk_scores = res.topk_scores + 1
            if res.hits is not None:
                # One made-up hit (row 0, alignment 0, score 0) for every
                # pattern of the launch.
                n = res.plan.n_patterns if res.hits.shape[1] == 4 else 1
                bogus = np.zeros((n, res.hits.shape[1]), res.hits.dtype)
                if res.hits.shape[1] == 4:
                    bogus[:, 2] = np.arange(n)
                res.hits = np.concatenate([res.hits, bogus])
            return res
        monkeypatch.setattr(engine.CompiledMatch, "run", altered)
    elif fault == "half":
        run_group = service.MatchService._run_group

        def half(self, grp):
            keep = grp[:max(1, len(grp) // 2)]
            run_group(self, keep)
            for p, src in zip(grp[len(keep):], itertools.cycle(keep)):
                self._complete(p, src.ticket.result, cached=False)
        monkeypatch.setattr(service.MatchService, "_run_group", half)


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_fault_is_not_correct(cell, fault, tree, monkeypatch, capsys):
    from bench import run
    monkeypatch.setattr(run, "look_for_chip", tiny.cpu_chip)
    monkeypatch.setattr(run, "use_compile_cache", lambda root: "off")
    plant(fault, monkeypatch)
    rc, res, err = run_cell(run, tree, cell, capsys, seconds=1.5)
    assert rc == 0 and res["correct"] is False, err
    bad = {k for k, v in res["checks"].items() if v["value"] > v["limit"]}
    assert bad, res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell, tree):
    import jax

    from bench import check, run
    cs = spec.load_cell(cell, tree)
    out = run.run_cell(cs, SEED, 1.0, False, jax.devices()[:1],
                       tiny.CPU_PEAKS, control=True)
    assert out["correct"] is True
    assert check.is_correct(out["control"]) is False, out["control"]
