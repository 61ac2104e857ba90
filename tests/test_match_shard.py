"""Sharded-vs-single-shard oracle equivalence (DESIGN.md Sec. 3h).

The mesh-sharded match stack must be *bit-identical* to the single-shard
engine: cyclic row placement, shard-local kernels under shard_map, the
survivor union, and the cross-shard top-k merge are all layout/execution
changes, never semantic ones.  Every test here runs the same query on a
1-shard engine and on 2- and 4-shard row meshes and asserts exact
equality -- backends x reductions x predicates x growth.

Needs forced host devices (tests/conftest.py sets
``--xla_force_host_platform_device_count=8``); skips with a named reason
when fewer devices are available.
"""

from __future__ import annotations

import dataclasses
import warnings

import jax
import numpy as np
import pytest

from repro.distributed import sharding as _sharding
from repro.match.engine import MatchEngine
from repro.match.query import MatchQuery
from repro.match.service import MatchService


def row_mesh(n_shards: int):
    if len(jax.devices()) < n_shards:
        pytest.skip(f"needs >= {n_shards} devices "
                    "(forced host devices; see tests/conftest.py)")
    from repro.launch.mesh import make_row_mesh
    return make_row_mesh(n_shards)


def corpus(n_rows: int, seed: int, chars: int = 64):
    rng = np.random.default_rng(seed)
    frags = rng.integers(0, 4, (n_rows, chars), np.uint8)
    # Plant a pattern a few times so threshold/topk have real hits.
    pat = frags[n_rows // 3, 10:26].copy()
    for r in (0, n_rows // 2, n_rows - 1):
        frags[r, 20:36] = pat
    return frags, pat


def engines(frags, n_shards):
    e1 = MatchEngine(frags.copy())
    es = MatchEngine(frags.copy(), mesh=row_mesh(n_shards))
    assert es.n_shards == n_shards
    return e1, es


def assert_result_equal(r1, rs):
    for f in ("scores", "best_locs", "best_scores", "topk_rows",
              "topk_scores", "hits"):
        a, b = getattr(r1, f), getattr(rs, f)
        assert (a is None) == (b is None), f
        if a is not None:
            np.testing.assert_array_equal(a, b, err_msg=f)


class TestCyclicLayout:
    """The layout helpers are each other's inverses and match the map
    r -> (r % S) * J + r // S."""

    def test_permute_roundtrip(self):
        rng = np.random.default_rng(0)
        a = rng.integers(0, 100, (24, 3))
        for s in (1, 2, 4, 8):
            np.testing.assert_array_equal(
                _sharding.cyclic_unpermute(
                    _sharding.cyclic_permute(a, s), s), a)

    def test_physical_rows_match_permute(self):
        rng = np.random.default_rng(1)
        a = rng.integers(0, 100, (24,))
        for s in (2, 4):
            phys = _sharding.cyclic_physical_rows(np.arange(24), s, 24 // s)
            np.testing.assert_array_equal(
                _sharding.cyclic_permute(a, s)[phys], a)


@pytest.mark.parametrize("n_shards", [2, 4])
@pytest.mark.parametrize("backend", ["swar", "mxu", "ref"])
class TestBackendEquivalence:
    def test_full_scores(self, backend, n_shards):
        frags, pat = corpus(100, seed=10)
        e1, es = engines(frags, n_shards)
        np.testing.assert_array_equal(
            np.asarray(e1.scores(pat, backend=backend)),
            np.asarray(es.scores(pat, backend=backend)))

    def test_reductions(self, backend, n_shards):
        frags, pat = corpus(100, seed=11)
        e1, es = engines(frags, n_shards)
        # filter=False pins the scan path: with filter=None the planner may
        # legitimately pick different strategies at different shard counts
        # (per-shard pricing), and the filtered path's survivor-only
        # best_locs would then differ in shape while the deliverable
        # (hits) stays identical.  TestFilteredPath covers the other leg.
        for q in (MatchQuery.exact(pat, reduction="best", backend=backend),
                  MatchQuery.exact(pat, reduction="topk", k=7,
                                   backend=backend),
                  MatchQuery.exact(pat, reduction="threshold", threshold=14,
                                   backend=backend, filter=False)):
            assert_result_equal(e1.match(q), es.match(q))

    def test_batched_coalesced(self, backend, n_shards):
        frags, pat = corpus(100, seed=12)
        rng = np.random.default_rng(13)
        pats = np.stack([pat] + [rng.integers(0, 4, 16, np.uint8)
                                 for _ in range(3)])
        e1, es = engines(frags, n_shards)
        q = MatchQuery.exact(pats, mode="batched", reduction="topk",
                             k=[5, 5, 5, 5], backend=backend)
        assert_result_equal(e1.match(q), es.match(q))


@pytest.mark.parametrize("n_shards", [2, 4])
class TestPredicatesAndSubsets:
    def test_wildcard_iupac(self, n_shards):
        frags, pat = corpus(100, seed=20)
        e1, es = engines(frags, n_shards)
        pstr = "".join("ACGT"[c] for c in pat)
        q = MatchQuery.iupac("N" + pstr[1:8] + "R" + pstr[9:],
                             reduction="best")
        assert_result_equal(e1.match(q), es.match(q))

    def test_rows_subset_gather(self, n_shards):
        frags, pat = corpus(100, seed=21)
        e1, es = engines(frags, n_shards)
        rows = [0, 3, 33, 50, 97, 99]
        q = MatchQuery.exact(pat, rows=rows, reduction="topk", k=4)
        assert_result_equal(e1.match(q), es.match(q))

    def test_topk_merge_is_bit_identical_on_ties(self, n_shards):
        # All-identical rows: every score ties, so the merge order is
        # decided purely by the (score desc, row asc) total order the
        # host merge must reproduce exactly.
        frags = np.tile(np.arange(4, dtype=np.uint8), (32, 16))
        pat = frags[0, :16].copy()
        e1, es = engines(frags, n_shards)
        q = MatchQuery.exact(pat, reduction="topk", k=9)
        r1, rs = e1.match(q), es.match(q)
        assert_result_equal(r1, rs)
        np.testing.assert_array_equal(rs.topk_rows, np.arange(9))


@pytest.mark.parametrize("n_shards", [2, 4])
class TestGrowth:
    def test_append_rows_equivalence_and_flat_pack_counters(self, n_shards):
        frags, pat = corpus(96, seed=30)
        e1, es = engines(frags, n_shards)
        # Force both device forms resident before growing.
        es.scores(pat, backend="swar")
        es.scores(np.stack([pat, pat]), backend="mxu")
        packs = es.corpus.host_pack_count
        rng = np.random.default_rng(31)
        for n in (5, 64, 300):   # in-place splice, then capacity growth
            more = rng.integers(0, 4, (n, 64), np.uint8)
            e1.corpus.append_rows(more)
            es.corpus.append_rows(more)
            np.testing.assert_array_equal(
                np.asarray(e1.scores(pat, backend="swar")),
                np.asarray(es.scores(pat, backend="swar")))
        # Growth splices rows per shard; it never repacks the resident
        # corpus (pack counters stay flat, DESIGN.md Sec. 3f + 3h).
        assert es.corpus.host_pack_count == packs

    def test_compiled_rows_subset_survives_growth(self, n_shards):
        # Capacity growth changes the per-shard stride, so the compiled
        # query's cached physical gather indices go stale and must be
        # rebuilt -- not reused -- after append_rows.
        frags, pat = corpus(96, seed=32)
        e1, es = engines(frags, n_shards)
        q = MatchQuery.exact(pat, rows=[1, 40, 95], reduction="best")
        c1, cs = e1.compile(q), es.compile(q)
        assert_result_equal(c1.run(), cs.run())
        more = np.random.default_rng(33).integers(0, 4, (500, 64), np.uint8)
        e1.corpus.append_rows(more)
        es.corpus.append_rows(more)
        assert_result_equal(c1.run(), cs.run())


@pytest.mark.parametrize("n_shards", [2, 4])
class TestFilteredPath:
    def test_filtered_threshold_equivalence(self, n_shards):
        frags, pat = corpus(200, seed=40)
        e1, es = engines(frags, n_shards)     # index auto-attached
        q = MatchQuery.exact(pat, reduction="threshold", threshold=14,
                             filter=True)
        r1, rs = e1.match(q), es.match(q)
        assert rs.plan.strategy == "filter", rs.plan.reason
        assert_result_equal(r1, rs)

    def test_sharded_filter_zero_false_negatives(self, n_shards):
        # Survivor union vs. exhaustive scan: identical hit sets, with
        # wildcards and after growth.
        frags, pat = corpus(200, seed=41)
        es = MatchEngine(frags.copy(), mesh=row_mesh(n_shards))
        more = np.random.default_rng(42).integers(0, 4, (100, 64), np.uint8)
        more[7, 5:21] = pat
        es.corpus.append_rows(more)
        pstr = "".join("ACGT"[c] for c in pat)
        # A group of several patterns at different thresholds: one filter
        # dispatch tests them all per shard.
        group = np.stack([pat, frags[50, 3:19], more[40, 30:46],
                          np.random.default_rng(43).integers(0, 4, 16,
                                                             np.uint8)])
        for q in (MatchQuery.exact(pat, reduction="threshold", threshold=13),
                  MatchQuery.iupac("N" + pstr[1:], reduction="threshold",
                                   threshold=13),
                  MatchQuery.exact(group, mode="batched",
                                   reduction="threshold",
                                   threshold=[14, 16, 15, 14])):
            filt = es.match(dataclasses.replace(q, filter=True))
            scan = es.match(dataclasses.replace(q, filter=False))
            np.testing.assert_array_equal(filt.hits, scan.hits)
            assert scan.plan.strategy == "scan"
        assert filt.plan.strategy == "filter" and filt.plan.n_patterns == 4
        assert {50, 240} <= set(filt.hits[:, 0].tolist())

    def test_sharded_filter_true_never_silent_scans(self, n_shards):
        # Regression (PR 6 satellite): before sharding-aware filtering,
        # a sharded engine silently dropped filter=True to a full scan.
        # Now it must either filter or raise a named error -- here the
        # index exists, so it filters.
        frags, pat = corpus(200, seed=43)
        es = MatchEngine(frags.copy(), mesh=row_mesh(n_shards))
        r = es.match(MatchQuery.exact(pat, reduction="threshold",
                                      threshold=14, filter=True))
        assert r.plan.strategy == "filter", r.plan.reason
        assert r.survivor_frac is not None
        # ... and when filtering is structurally impossible (index=False),
        # filter=True raises a named error rather than silently scanning.
        es2 = MatchEngine(frags.copy(), mesh=row_mesh(n_shards),
                          index=False)
        with pytest.raises(ValueError, match="cannot honor filter=True"):
            es2.match(MatchQuery.exact(pat, reduction="threshold",
                                       threshold=14, filter=True))


class TestSurfacing:
    def test_resolve_axis_warns_on_fallback(self):
        mesh = row_mesh(3)
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            eng = MatchEngine(np.zeros((10, 64), np.uint8), mesh=mesh)
        assert eng.n_shards == 1
        msgs = [str(w.message) for w in rec
                if issubclass(w.category, UserWarning)]
        assert any("rows" in m and "replication" in m for m in msgs), msgs

    def test_repr_and_result_surface_shards(self):
        frags, pat = corpus(64, seed=50)
        es = MatchEngine(frags, mesh=row_mesh(2))
        assert "shards=2" in repr(es)
        assert es.match(pat).n_shards == 2
        e1 = MatchEngine(frags.copy())
        assert e1.match(pat).n_shards == 1

    def test_service_reports_per_shard_rows(self):
        frags, pat = corpus(64, seed=51)
        es = MatchEngine(frags, mesh=row_mesh(4))
        svc = MatchService(es)
        rng = np.random.default_rng(52)
        for i in range(10):
            svc.ingest(rng.integers(0, 4, (1 + i % 3, 64), np.uint8))
        svc.submit(pat, reduction="best")
        svc.flush()
        snap = svc.stats.snapshot()
        assert snap["n_shards"] == 4
        assert sum(snap["shard_rows"]) == es.corpus.n_rows
        assert snap["shard_balance"] <= 1.1
        # Cyclic placement: shard s holds ceil((n - s) / S) rows exactly.
        np.testing.assert_array_equal(
            snap["shard_rows"], es.shard_live_rows())
