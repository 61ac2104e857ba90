"""Decide ``correct``: the window's answers against the plain reference.

The reference (``bench/reference.py``) runs once the window has closed
and the program's state is freed, over the sampled requests' patterns in
one pass over the corpus.  Every number compared here is a count of
things that differ from the reference, and every limit is 0: the
configuration states exact answers.

Numbers (each appears only where the cell has something to compare):

* ``lost``: reads of the window that errored or never completed.
* ``topk_wrong``: sampled top-k answers whose rows or scores differ.
* ``rowbest_wrong``: sampled answers whose per-row best score or first
  best alignment differs on any row.
* ``hits_wrong``: sampled threshold answers whose (row, alignment,
  score) set differs.
* ``filter_missed``: rows holding a reference hit that the q-gram filter
  did not pass to the verify stage (the filter's no-false-negative
  guarantee).  The program reports one survivor set per coalesced
  launch, the union over its reads, so a read's hit rows are held
  against that union.
* ``no_sample``: 1 when the window answered nothing to compare.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from bench import reference
from bench.drive import Sample, Window


def _rows_set(h: Optional[np.ndarray]) -> set:
    if h is None:
        return set()
    h = np.asarray(h).reshape(-1, 3)
    return {(int(r), int(l), int(s)) for r, l, s in h}


def compare(samples: List[Sample], ref: List[dict]) -> Dict[str, int]:
    """Counts of sampled answers that differ from the reference's."""
    out: Dict[str, int] = {}
    for s, want in zip(samples, ref):
        got = s.answer
        if "topk_rows" in got:
            ok = (np.array_equal(np.asarray(got["topk_rows"]),
                                 want["topk_rows"])
                  and np.array_equal(np.asarray(got["topk_scores"]),
                                     want["topk_scores"]))
            out["topk_wrong"] = out.get("topk_wrong", 0) + int(not ok)
        if "best" in got:
            ok = (np.array_equal(np.asarray(got["best"]), want["best"])
                  and np.array_equal(np.asarray(got["loc"]), want["loc"]))
            out["rowbest_wrong"] = out.get("rowbest_wrong", 0) + int(not ok)
        if "hits" in got:
            ok = _rows_set(got["hits"]) == _rows_set(want["hits"])
            out["hits_wrong"] = out.get("hits_wrong", 0) + int(not ok)
            surv = got.get("survivor_rows")
            if surv is not None:
                need = np.unique(want["hits"][:, 0])
                out["filter_missed"] = out.get("filter_missed", 0) + int(
                    np.setdiff1d(need, np.asarray(surv)).size)
    return out


def reference_answers(samples: List[Sample], corpus: np.ndarray,
                      precision: str = "exact") -> List[dict]:
    if not samples:
        return []
    masks = np.stack([s.masks for s in samples])
    return reference.answers(corpus, masks, [s.spec for s in samples],
                             precision=precision)


def control_samples(samples: List[Sample], corpus: np.ndarray
                    ) -> List[Sample]:
    """The control in the program's place: the reference's own answers,
    computed with scores rounded through float8, as if the timed path
    had produced them."""
    ctl = reference_answers(samples, corpus, precision="fp8")
    out = []
    for s, a in zip(samples, ctl):
        ans = {k: a[k] for k in s.answer if k in a}
        if "survivor_rows" in s.answer:
            ans["survivor_rows"] = s.answer["survivor_rows"]
        out.append(Sample(s.masks, s.spec, ans, s.cached))
    return out


def numbers(win: Window, samples: List[Sample], ref: List[dict]
            ) -> Dict[str, dict]:
    """Every compared number with its limit, in a fixed order."""
    found = compare(samples, ref)
    vals = {"lost": win.failed}
    for name in ("topk_wrong", "rowbest_wrong", "hits_wrong",
                 "filter_missed"):
        if name in found:
            vals[name] = found[name]
    vals["no_sample"] = int(not samples)
    return {k: {"value": v, "limit": 0} for k, v in vals.items()}


def is_correct(nums: Dict[str, dict]) -> bool:
    return all(v["value"] <= v["limit"] for v in nums.values())
