"""The generator: seeds reproduce their data, and every seed gets the same
error counts in another order."""

from __future__ import annotations

import itertools
import json

import numpy as np
import pytest

from bench import gen, spec

BIG_SEED = 2 ** 31 + 12345


def _traffic(name):
    return json.loads((spec.BENCH_DIR / "traffic" / f"{name}.json")
                      .read_text())


def test_rows_hold_every_window_wholly():
    G, F, P, stride = 3000, 48, 16, 33
    g = gen.genome(5, G, F, stride)
    rows = gen.fold(g, G, F, stride)
    assert rows.shape == (gen.n_rows(G, F, stride), F)
    for pos in range(0, G - P + 1, 7):
        r = pos // stride
        assert np.array_equal(rows[r, pos - r * stride:pos - r * stride + P],
                              g[pos:pos + P])


def test_row_counts_of_the_configurations():
    for name, want in (("readmap-chr1", 1585710),):
        c = json.loads((spec.BENCH_DIR / "configs" / f"{name}.json")
                       .read_text())
        assert gen.n_rows(c["genome_bp"], c["fragment_chars"],
                          c["row_stride"]) == c["rows"] == want


@pytest.mark.parametrize("mix", ["topk-b16", "v2-b16"])
def test_reads_reproduce_from_the_seed(mix):
    tr = _traffic(mix)
    G, F, P, stride = 4000, 64, 30, 35
    g = gen.genome(BIG_SEED, G, F, stride)
    a = list(itertools.islice(gen.read_stream(BIG_SEED, g, G, P, tr), 90))
    b = list(itertools.islice(gen.read_stream(BIG_SEED, g, G, P, tr), 90))
    c = list(itertools.islice(gen.read_stream(BIG_SEED + 1, g, G, P, tr),
                              90))
    assert all(np.array_equal(x.masks, y.masks) and x.origin == y.origin
               for x, y in zip(a, b))
    assert any(x.origin != y.origin for x, y in zip(a, c))
    for r in a:
        if r.origin >= 0:
            codes = np.log2(r.masks).astype(np.uint8)
            assert (codes != g[r.origin:r.origin + P]).sum() == r.n_err


def test_error_cycle_has_fixed_shares():
    tr = _traffic("v2-b16")
    G, F, P, stride = 4000, 64, 30, 35
    for seed in (1, 2, BIG_SEED):
        g = gen.genome(seed, G, F, stride)
        reads = list(itertools.islice(
            gen.read_stream(seed, g, G, P, tr), tr["errors"]["block"]))
        counts = sorted(r.n_err for r in reads)
        assert counts == sorted([-1] * 3 + [0] * 9 + [1] * 9 + [2] * 9)
