"""The plain reference against brute-force loops, on the CPU."""

from __future__ import annotations

import numpy as np
import pytest

from bench import gen, reference


def _case(seed=0, R=9, F=24, P=7, Q=3, iupac=True):
    r = np.random.default_rng(seed)
    rows = r.integers(0, 4, (R, F), np.uint8)
    masks = gen.exact_masks(r.integers(0, 4, (Q, P), np.uint8))
    if iupac:
        masks[0, 2] = 0b1111       # N
        masks[1, 4] = 0b0101       # R: A or G
    return rows, masks


def test_scores_np_matches_loops():
    rows, masks = _case()
    np.testing.assert_array_equal(reference.scores_np(rows, masks),
                                  reference.scores_loops(rows, masks))


@pytest.mark.parametrize("precision", reference.PRECISIONS)
def test_scan_matches_loops(precision):
    rows, masks = _case(seed=1, R=37, F=40, P=9, Q=5)
    loops = reference.scores_loops(rows, masks)
    if precision == "fp8":
        loops = reference.round_fp8(loops)
    thr = np.array([6, 7, 5.5, 9, 20])
    sc = reference.scan(rows, masks, thr, precision=precision, block_rows=16)
    np.testing.assert_array_equal(sc.best, loops.max(1))
    np.testing.assert_array_equal(sc.loc, loops.argmax(1))
    np.testing.assert_array_equal(sc.hot, (loops >= np.ceil(thr)).any(1))


def test_topk_breaks_ties_to_the_lower_row():
    best = np.array([3, 7, 5, 7, 7, 1, 5], np.int32)
    rows, scores = reference.topk(best, 4)
    order = sorted(range(best.size), key=lambda i: (-best[i], i))[:4]
    assert rows.tolist() == order
    assert scores.tolist() == [7, 7, 7, 5]


def test_hits_match_loops():
    rows, masks = _case(seed=2, R=20, F=30, P=6, Q=1, iupac=False)
    loops = reference.scores_loops(rows, masks)[:, :, 0]
    want = [(r, l, loops[r, l]) for r in range(20) for l in range(25)
            if loops[r, l] >= 4]
    sc = reference.scan(rows, masks, [4])
    got = reference.hits(rows, masks[0], 4, np.flatnonzero(sc.hot[:, 0]))
    assert [tuple(h) for h in got.tolist()] == want


def test_planted_read_is_found_in_its_row():
    G, F, P, stride = 5000, 64, 20, 45
    g = gen.genome(7, G, F, stride)
    rows = gen.fold(g, G, F, stride)
    pos = 3001
    read = gen.exact_masks(g[pos:pos + P])
    row = pos // stride
    ans = reference.answers(rows, read[None], [{"reduction": "topk", "k": 1}])
    assert ans[0]["topk_rows"].tolist() == [row]
    assert ans[0]["topk_scores"].tolist() == [P]
    thr = reference.answers(rows, read[None],
                            [{"reduction": "threshold", "threshold": P}])
    assert [int(row), pos - row * stride, P] in thr[0]["hits"].tolist()


def test_fp8_control_breaks_exact_scores():
    """Scores near a 100-char read's full match do not survive float8."""
    exact = np.arange(90, 101)
    rounded = reference.round_fp8(exact)
    assert (rounded != exact).sum() >= 8
    assert reference.round_fp8(np.arange(0, 17)).tolist() == list(range(17))
