"""Q-gram filter index tests (DESIGN.md Sec. 3g).

The load-bearing invariants:

* **zero false negatives** -- filtered threshold execution produces
  ``hits`` bit-identical to the full scan (and to the NumPy oracle) on
  every backend, for exact and wildcard/IUPAC queries, before and after
  corpus growth;
* the index is **incrementally resident** -- built lazily once
  (``sig_pack_count <= 1``), kept current by ``append_rows`` / ``set_rows``
  splices of exactly the touched rows, zero-extended across capacity
  growth, dropped by ``invalidate``;
* the **planner's two-stage cost model** picks filter-then-verify for
  selective queries at scale, falls back to the full scan for dense /
  unprunable / ineligible queries, and honors the query hints;
* the **service** routes eligible queries through the index transparently
  and reports filter hit-rate / survivor fraction (plus the per-tick
  launch and cache-hit-rate satellites).
"""

import numpy as np
import pytest

from repro.core import encoding
from repro.core.matcher import sliding_scores, sliding_scores_masks
from repro.kernels.filter_qgram import (FILTER_BLOCK_ROWS, SIG_ROW_TILE,
                                        filter_qgram, filter_qgram_ref,
                                        padded_rows, pattern_operands,
                                        survivor_rows)
from repro.match import (CorpusIndex, MatchEngine, MatchQuery,
                         MatchService, PackedCorpus, Planner,
                         build_query_filter)
from repro.match.index import (binom_cdf, hash_bits, qgram_values,
                               row_signatures)

R0, F, P = 48, 96, 16


def make_engine(r=R0, f=F, seed=0, planted=(), pat=None, **kw):
    rng = np.random.default_rng(seed)
    frags = rng.integers(0, 4, (r, f), np.uint8)
    if pat is not None:
        for row in planted:
            off = int(rng.integers(0, f - len(pat) + 1))
            frags[row, off:off + len(pat)] = pat
    return rng, frags, MatchEngine(frags, **kw)


def naive_row_bits(row, q, n_bits):
    """Set of signature bits a row's q-grams occupy (python reference)."""
    vals = [int(qgram_values(row[j:j + q], q)[0])
            for j in range(len(row) - q + 1)]
    return set(int(b) for b in hash_bits(np.asarray(vals, np.uint32),
                                         n_bits))


def unpack_sig(words):
    """(Wb,) uint32 signature words -> set of set bit indices."""
    return {w * 32 + b for w in range(len(words)) for b in range(32)
            if (int(words[w]) >> b) & 1}


class TestSignatures:
    def test_row_signature_matches_naive(self):
        rng = np.random.default_rng(1)
        rows = rng.integers(0, 4, (5, 40), np.uint8)
        words, counts = row_signatures(rows, 4, 256)
        for r in range(5):
            want = naive_row_bits(rows[r], 4, 256)
            assert unpack_sig(words[r]) == want
            assert counts[r] == len(want)

    def test_query_signature_drops_wildcard_spanning_qgrams(self):
        pat = np.random.default_rng(7).integers(0, 4, 12, np.uint8)
        masks = (np.uint8(1) << pat).astype(np.uint8)
        full = build_query_filter(masks[None, :], [12.0], 4, 256)
        masks_w = masks.copy()
        masks_w[5] = 0b1111                   # N wildcard at position 5
        part = build_query_filter(masks_w[None, :], [12.0], 4, 256)
        # Grams starting at 2..5 span position 5; of 9 gram positions, 4
        # are dropped.  Remaining bits are a subset of the exact query's.
        assert part.n_bits[0] < full.n_bits[0]
        assert unpack_sig(part.qsig_words[0]) <= \
            unpack_sig(full.qsig_words[0])

    def test_all_wildcard_pattern_has_no_bits(self):
        masks = np.full((1, 8), 0b1111, np.uint8)
        ops = build_query_filter(masks, [8.0], 4, 256)
        assert ops.n_bits == (0,)

    def test_slack_from_threshold(self):
        masks = (np.uint8(1) << (np.arange(10, dtype=np.uint8) % 4))
        ops = build_query_filter(masks[None, :], [10.0, 8.0, 10.5], 4, 256)
        assert ops.slacks == (0, 8, -1)       # e=0, e=2 -> 2q, unsatisfiable

    def test_binom_cdf_sane(self):
        assert binom_cdf(-1, 10, 0.5) == 0.0
        assert binom_cdf(10, 10, 0.5) == 1.0
        assert abs(binom_cdf(5, 10, 0.5) - 0.623046875) < 1e-9


def union_ref(sigs_cols, qsig_words, slacks):
    """OR of ``filter_qgram_ref`` over a group's patterns ((R,) int32)."""
    rows = np.ascontiguousarray(np.asarray(sigs_cols).T)
    out = np.zeros(rows.shape[0], np.int32)
    for q, s in zip(qsig_words, slacks):
        out |= filter_qgram_ref(rows, q[None, :], s)
    return out


def run_filter(sigs_cols, qsig_words, slacks):
    """The kernel's survivor ids for one group (int8 flags, 1 bit/row)."""
    q, s = pattern_operands(qsig_words, slacks)
    flags = np.asarray(filter_qgram(sigs_cols, q, s, interpret=True))
    assert flags.dtype == np.int8
    assert flags.shape == (1, sigs_cols.shape[1] // 8)
    return survivor_rows(flags, sigs_cols.shape[0])


def edge_group(Q, R, seed):
    """Random group plus rows at tile and chunk edges on its slack edge."""
    rng = np.random.default_rng(seed)
    # Dense-ish row signatures and sparse-ish pattern bits, so absent
    # counts straddle the slacks and both outcomes occur.
    sigs = (rng.integers(0, 2**32, (8, R), dtype=np.uint32)
            | rng.integers(0, 2**32, (8, R), dtype=np.uint32))
    qsig = (rng.integers(0, 2**32, (Q, 8), dtype=np.uint32)
            & rng.integers(0, 2**32, (Q, 8), dtype=np.uint32))
    slacks = rng.integers(-3, 12, Q).tolist()
    slacks[0] = s0 = max(slacks[0], 0)
    # Pattern 0 lacks exactly s0 of its bits in the even edge columns (so
    # it admits them) and s0 + 1 in the odd ones.
    need = np.unpackbits(qsig[0].view(np.uint8), bitorder="little")
    for col in (0, 127, 128, 511, 512, 1023, 1024, 2047, 2048, R - 1):
        if col >= R:
            continue
        keep = np.ones_like(need)
        keep[np.flatnonzero(need)[:s0 + col % 2]] = 0
        sigs[:, col] = np.packbits(keep, bitorder="little").view(np.uint32)
    return sigs, qsig, slacks


class TestFilterKernel:
    def test_kernel_matches_ref(self):
        # One pattern at each slack, negative (unsatisfiable) included.
        rng = np.random.default_rng(2)
        sigs = rng.integers(0, 2**32, (8, SIG_ROW_TILE * 2),
                            dtype=np.uint32)
        qsig = rng.integers(0, 2**32, (1, 8), dtype=np.uint32)
        for slack in (0, 3, 17, -1):
            np.testing.assert_array_equal(
                run_filter(sigs, qsig, [slack]),
                np.flatnonzero(filter_qgram_ref(
                    np.ascontiguousarray(sigs.T), qsig, slack)))

    @pytest.mark.parametrize("Q,R", [
        (1, SIG_ROW_TILE * 2), (3, SIG_ROW_TILE),
        (8, SIG_ROW_TILE * 3), (16, SIG_ROW_TILE * 4),
        (17, FILTER_BLOCK_ROWS * 2), (100, SIG_ROW_TILE)])
    def test_group_matches_union_of_ref(self, Q, R):
        # Mixed slacks (negative ones too), pad patterns up to Q_pad, rows
        # at tile and chunk edges, and (Q = 100) two pattern blocks OR-ed
        # into one row tile: bit-identical to the OR of the oracle.
        sigs, qsig, slacks = edge_group(Q, R, seed=Q)
        want = union_ref(sigs, qsig, slacks)
        np.testing.assert_array_equal(run_filter(sigs, qsig, slacks),
                                      np.flatnonzero(want))
        assert 0 < want.sum() < R

    def test_pad_patterns_never_pass(self):
        # Three real patterns pad to eight; the pads' all-zero signatures
        # would pass every row at any slack >= 0, so they carry -1.
        q, s = pattern_operands(np.zeros((3, 8), np.uint32), [-1, -1, -1])
        assert q.shape == (8, 8, 1) and s.shape == (8, 1, 1)
        assert (s[3:] == -1).all() and not q[3:].any()
        sigs = np.zeros((8, SIG_ROW_TILE), np.uint32)
        assert run_filter(sigs, np.zeros((3, 8), np.uint32),
                          [-1, -1, -1]).size == 0
        np.testing.assert_array_equal(
            run_filter(sigs, np.zeros((3, 8), np.uint32), [-1, 0, -1]),
            np.arange(SIG_ROW_TILE))

    def test_slacks_share_one_program(self):
        # Slacks are operands: two slack sets at the same Q_pad run the
        # same compiled program.
        rng = np.random.default_rng(8)
        sigs = rng.integers(0, 2**32, (8, SIG_ROW_TILE), dtype=np.uint32)
        qsig = rng.integers(0, 2**32, (5, 8), dtype=np.uint32)
        run_filter(sigs, qsig, [4] * 5)
        n0 = filter_qgram._cache_size()
        for slacks in ([8] * 5, [12, 0, -1, 3, 30]):
            np.testing.assert_array_equal(
                run_filter(sigs, qsig, slacks),
                np.flatnonzero(union_ref(sigs, qsig, slacks)))
        assert filter_qgram._cache_size() == n0

    def test_sharded_flags_decode_to_logical_rows(self):
        # Two shard blocks of the cyclic layout, each its own dispatch:
        # slot j of block s is logical row j * 2 + s.
        blocks = [edge_group(4, SIG_ROW_TILE * 2, seed=30 + s)[0]
                  for s in range(2)]
        _, qsig, slacks = edge_group(4, SIG_ROW_TILE * 2, seed=30)
        q, sl = pattern_operands(qsig, slacks)
        flags = np.concatenate([np.asarray(filter_qgram(
            b, q, sl, interpret=True)) for b in blocks], axis=1)
        want = np.sort(np.concatenate([
            np.flatnonzero(union_ref(b, qsig, slacks)) * 2 + s
            for s, b in enumerate(blocks)]))
        np.testing.assert_array_equal(survivor_rows(flags, 8, 2), want)

    @pytest.mark.parametrize("Wb", [1, 8, 16, 128])
    def test_vmem_blocks_are_bounded_at_any_group_size(self, Wb):
        # The pattern block is fixed by Wb, not by Q: a group of any size
        # streams through the same VMEM, and the row tile takes only what
        # the pattern block leaves.
        from repro.kernels.filter_qgram import (
            _PATTERN_BUDGET, PATTERN_TILE, flag_tile, pattern_block)
        from repro.kernels.tiling import VMEM_BLOCK_BUDGET, lane_bytes
        pb = pattern_block(Wb)
        slab = lane_bytes(1) * (-(-Wb // 8) * 8 + 8)
        assert pb * slab <= _PATTERN_BUDGET or pb == PATTERN_TILE
        assert Wb > 8 or pb == 64
        tile = flag_tile(FILTER_BLOCK_ROWS * 4, Wb)
        assert tile * (-(-Wb // 8) * 8 * 4 + 8) <= (
            VMEM_BLOCK_BUDGET - _PATTERN_BUDGET) or tile == SIG_ROW_TILE

    def test_planner_prices_pad_patterns(self):
        # A lone query runs eight patterns (seven pads); the price says so.
        from repro.core.tech import TPU_V5E
        from repro.match.planner import analytic_filter_seconds
        r = TPU_V5E
        R = 1 << 24
        one = analytic_filter_seconds(r, R, 8, 1)
        assert one == analytic_filter_seconds(r, R, 8, 8)
        assert one < analytic_filter_seconds(r, R, 8, 9)

    def test_kernel_rejects_unpadded_rows(self):
        q, s = pattern_operands(np.zeros((1, 8), np.uint32), [0])
        with pytest.raises(ValueError, match="padded"):
            filter_qgram(np.zeros((8, 7), np.uint32), q, s, interpret=True)

    def test_padded_rows_keep_the_grid_coarse(self):
        assert padded_rows(48) == SIG_ROW_TILE
        assert padded_rows(1585712) % FILTER_BLOCK_ROWS == 0
        assert padded_rows(1585712) - 1585712 < FILTER_BLOCK_ROWS
        assert padded_rows(3000) == 4096


class TestIndexResidency:
    def test_lazy_pack_once(self):
        _, _, eng = make_engine()
        ix = eng.index
        assert ix.sig_pack_count == 0         # nothing until first use
        ix.signatures()
        ix.signatures()
        assert ix.sig_pack_count == 1

    def test_append_splices_only_touched_rows(self):
        rng, frags, eng = make_engine(seed=3)
        ix = eng.index
        ix.signatures()
        new = rng.integers(0, 4, (3, F), np.uint8)
        eng.corpus.append_rows(new)
        assert ix.sig_pack_count == 1         # no repack
        assert ix.row_update_count == 3
        got = np.asarray(ix.signatures())[:, R0:R0 + 3].T
        want, _ = row_signatures(new, ix.q, ix.n_bits)
        np.testing.assert_array_equal(got, want)

    def test_set_rows_replaces_signature(self):
        rng, frags, eng = make_engine(seed=4)
        ix = eng.index
        ix.signatures()
        new = rng.integers(0, 4, (1, F), np.uint8)
        eng.corpus.set_rows(5, new)
        got = np.asarray(ix.signatures())[:, 5]
        want, _ = row_signatures(new, ix.q, ix.n_bits)
        np.testing.assert_array_equal(got, want[0])

    def test_capacity_growth_extends_device_form(self):
        rng, frags, eng = make_engine(seed=5)
        ix = eng.index
        ix.signatures()
        rows0 = ix._sigs.shape[1]
        while eng.corpus.capacity_padded <= rows0:   # force a device extend
            eng.corpus.append_rows(rng.integers(0, 4, (32, F), np.uint8))
        assert ix._sigs.shape[1] >= ix._rows_padded
        assert ix._sigs.shape[1] % SIG_ROW_TILE == 0
        assert ix.sig_pack_count == 1

    def test_invalidate_drops_form(self):
        _, _, eng = make_engine(seed=6)
        ix = eng.index
        ix.signatures()
        eng.corpus.invalidate()
        assert ix._sigs is None
        ix.signatures()
        assert ix.sig_pack_count == 2

    def test_index_validates_parameters(self):
        corpus = PackedCorpus(np.zeros((4, 16), np.uint8))
        with pytest.raises(ValueError, match="power of two"):
            CorpusIndex(corpus, n_bits=48)
        with pytest.raises(ValueError, match="q must be"):
            CorpusIndex(corpus, q=0)
        with pytest.raises(ValueError, match="shorter than"):
            CorpusIndex(PackedCorpus(np.zeros((4, 2), np.uint8)), q=4)

    def test_engine_rejects_foreign_index(self):
        a = PackedCorpus(np.zeros((4, 16), np.uint8))
        b = np.zeros((4, 16), np.uint8)
        ix = CorpusIndex(a)
        with pytest.raises(ValueError, match="different corpus"):
            MatchEngine(b, index=ix)

    def test_engines_share_one_index_and_detach_stops_updates(self):
        rng = np.random.default_rng(7)
        corpus = PackedCorpus(rng.integers(0, 4, (R0, F), np.uint8))
        a, b = MatchEngine(corpus), MatchEngine(corpus)
        assert a.index is b.index                  # no observer stacking
        assert len(corpus._indexes) == 1
        old = a.index
        old.signatures()
        corpus.detach_index(old)
        corpus.append_rows(rng.integers(0, 4, (2, F), np.uint8))
        assert old.row_update_count == 0           # no longer notified


THR = float(P)


class TestFilteredOracle:
    """Filtered == full scan == NumPy oracle, bit for bit."""

    @pytest.mark.parametrize("backend", ["swar", "mxu", "ref"])
    def test_exact_threshold_all_backends(self, backend):
        rng = np.random.default_rng(10)
        pat = rng.integers(0, 4, P, np.uint8)
        _, frags, eng = make_engine(seed=10, planted=(5, 17), pat=pat)
        oracle = sliding_scores(frags, pat)
        for thr in (THR, THR - 2.0):
            fil = eng.match(MatchQuery.exact(
                pat, reduction="threshold", threshold=thr, filter=True,
                backend=backend))
            scan = eng.match(MatchQuery.exact(
                pat, reduction="threshold", threshold=thr, filter=False,
                backend=backend))
            assert fil.plan.strategy == "filter"
            assert scan.plan.strategy == "scan"
            np.testing.assert_array_equal(fil.hits, scan.hits)
            want = np.argwhere(oracle >= thr)
            np.testing.assert_array_equal(scan.hits[:, :2], want)
        assert {5, 17} <= set(fil.survivor_rows.tolist())
        assert 0 < fil.survivor_frac < 1

    @pytest.mark.parametrize("backend", ["swar", "mxu", "ref"])
    def test_wildcard_threshold_all_backends(self, backend):
        rng = np.random.default_rng(11)
        pat = rng.integers(0, 4, P, np.uint8)
        _, frags, eng = make_engine(seed=11, planted=(3,), pat=pat)
        masks = (np.uint8(1) << pat).astype(np.uint8)
        masks[[2, 9]] = 0b1111                # N wildcards
        oracle = sliding_scores_masks(frags, masks)
        fil = eng.match(MatchQuery.from_masks(
            masks, reduction="threshold", threshold=THR, filter=True,
            backend=backend))
        scan = eng.match(MatchQuery.from_masks(
            masks, reduction="threshold", threshold=THR, filter=False,
            backend=backend))
        assert fil.plan.strategy == "filter"
        np.testing.assert_array_equal(fil.hits, scan.hits)
        np.testing.assert_array_equal(
            scan.hits[:, :2], np.argwhere(oracle >= THR))
        assert (fil.hits[:, 0] == 3).any()

    def test_iupac_query_filters(self):
        eng = MatchEngine(np.tile(encoding.encode_dna("ACGTACGTACGT"),
                                  (12, 1)))
        fil = eng.match(MatchQuery.iupac("ACGTRCGT", reduction="threshold",
                                         threshold=8, filter=True))
        scan = eng.match(MatchQuery.iupac("ACGTRCGT", reduction="threshold",
                                          threshold=8, filter=False))
        assert fil.plan.strategy == "filter"
        np.testing.assert_array_equal(fil.hits, scan.hits)
        assert fil.hits.shape[0] == 12 * 2    # two alignments per row

    def test_batched_per_query_thresholds(self):
        rng = np.random.default_rng(12)
        pats = rng.integers(0, 4, (3, P), np.uint8)
        _, frags, eng = make_engine(seed=12)
        frags[7, 5:5 + P] = pats[0]
        frags[30, 11:11 + P] = pats[2]
        eng = MatchEngine(frags)
        thrs = [THR, THR - 1.0, THR]
        fil = eng.match(MatchQuery.exact(
            pats, mode="batched", reduction="threshold", threshold=thrs,
            filter=True))
        scan = eng.match(MatchQuery.exact(
            pats, mode="batched", reduction="threshold", threshold=thrs,
            filter=False))
        assert fil.plan.strategy == "filter"
        np.testing.assert_array_equal(fil.hits, scan.hits)
        assert {7, 30} <= set(fil.hits[:, 0].tolist())

    def test_zero_survivors_well_formed(self):
        rng = np.random.default_rng(13)
        _, frags, eng = make_engine(seed=13)
        pat = rng.integers(0, 4, P, np.uint8)   # no planted needle
        res = eng.match(MatchQuery.exact(
            pat, reduction="threshold", threshold=THR, filter=True))
        if res.survivor_frac == 0.0:            # typical for random data
            assert res.hits.shape == (0, 3)
            assert res.best_scores.shape == (0,)
            assert res.survivor_rows.shape == (0,)
        scan = eng.match(MatchQuery.exact(
            pat, reduction="threshold", threshold=THR, filter=False))
        np.testing.assert_array_equal(res.hits, scan.hits)

    def test_unsatisfiable_threshold_prunes_everything(self):
        rng = np.random.default_rng(14)
        _, frags, eng = make_engine(seed=14)
        pat = rng.integers(0, 4, P, np.uint8)
        res = eng.match(MatchQuery.exact(
            pat, reduction="threshold", threshold=P + 1.0, filter=True))
        assert res.survivor_frac == 0.0 and res.hits.shape == (0, 3)

    def test_hits_sorted_like_full_scan(self):
        """Survivor order is ascending corpus rows, so hit order matches
        the chunk-streamed full scan exactly (part of bit-identity)."""
        rng = np.random.default_rng(15)
        pat = rng.integers(0, 4, P, np.uint8)
        _, frags, eng = make_engine(seed=15, planted=(40, 2, 21), pat=pat)
        fil = eng.match(MatchQuery.exact(
            pat, reduction="threshold", threshold=THR - 1, filter=True))
        assert (np.diff(fil.hits[:, 0]) >= 0).all()


class TestFilteredAcrossGrowth:
    def test_compiled_filter_survives_append(self):
        rng = np.random.default_rng(20)
        pat = rng.integers(0, 4, P, np.uint8)
        _, frags, eng = make_engine(seed=20, planted=(9,), pat=pat)
        cm = eng.compile(MatchQuery.exact(
            pat, reduction="threshold", threshold=THR, filter=True))
        r1 = cm.run()
        assert r1.plan.strategy == "filter"
        ops_before = cm._filter_ops
        planted = np.zeros(F, np.uint8)
        planted[4:4 + P] = pat
        eng.corpus.append_rows(planted)
        r2 = cm.run()                          # same compiled object
        assert r2.plan.strategy == "filter"
        assert cm._filter_ops is not None
        np.testing.assert_array_equal(
            cm._filter_ops.qsig_words, ops_before.qsig_words)
        assert (r2.hits[:, 0] == R0).any()     # new row's hit observed
        scan = eng.match(MatchQuery.exact(
            pat, reduction="threshold", threshold=THR, filter=False))
        np.testing.assert_array_equal(r2.hits, scan.hits)

    def test_append_while_filtering_no_repacks(self):
        rng = np.random.default_rng(21)
        pat = rng.integers(0, 4, P, np.uint8)
        _, frags, eng = make_engine(seed=21, planted=(1,), pat=pat)
        q = MatchQuery.exact(pat, reduction="threshold", threshold=THR,
                             filter=True)
        eng.match(q)
        for _ in range(3):
            row = np.zeros(F, np.uint8)
            row[7:7 + P] = pat
            eng.corpus.append_rows(row)
            fil = eng.match(q)
            scan = eng.match(MatchQuery.exact(
                pat, reduction="threshold", threshold=THR, filter=False))
            np.testing.assert_array_equal(fil.hits, scan.hits)
        assert eng.index.sig_pack_count == 1   # spliced, never repacked

    def test_selectivity_feedback_recorded(self):
        rng = np.random.default_rng(22)
        pat = rng.integers(0, 4, P, np.uint8)
        _, frags, eng = make_engine(seed=22, planted=(0, 1, 2, 3), pat=pat)
        assert eng.index.n_filter_runs == 0
        eng.match(MatchQuery.exact(pat, reduction="threshold",
                                   threshold=THR, filter=True))
        assert eng.index.n_filter_runs == 1
        assert eng.index.last_survivor_frac >= 4 / R0
        assert eng.index._calibration is not None


class TestPlannerStrategy:
    def big_engine(self, rows=20000, f=256):
        # Reserved capacity + live zero rows: planning never runs kernels,
        # so a large corpus is cheap to stand up for decision tests.
        rng = np.random.default_rng(30)
        return MatchEngine(rng.integers(0, 4, (rows, f), np.uint8))

    def test_selective_filters_dense_scans_at_scale(self):
        eng = self.big_engine()
        pat = np.random.default_rng(32).integers(0, 4, 32, np.uint8)
        sel = eng.compile(MatchQuery.exact(pat, reduction="threshold",
                                           threshold=32.0))
        dense = eng.compile(MatchQuery.exact(pat, reduction="threshold",
                                             threshold=5.0))
        assert sel.plan.strategy == "filter"
        assert sel.plan.filter_words == eng.index.sig_words
        assert sel.plan.est_survivor_frac < 0.01
        assert dense.plan.strategy == "scan"
        assert "filter" in sel.plan.reason

    def test_small_corpus_scans_without_hint(self):
        _, _, eng = make_engine()
        pat = np.arange(P, dtype=np.uint8) % 4
        cm = eng.compile(MatchQuery.exact(pat, reduction="threshold",
                                          threshold=THR))
        assert cm.plan.strategy == "scan"      # dispatch overhead dominates

    def test_filter_false_hint_always_scans(self):
        eng = self.big_engine()
        pat = np.arange(32, dtype=np.uint8) % 4
        cm = eng.compile(MatchQuery.exact(pat, reduction="threshold",
                                          threshold=32.0, filter=False))
        assert cm.plan.strategy == "scan"

    def test_index_disabled_engine_scans(self):
        rng = np.random.default_rng(31)
        eng = MatchEngine(rng.integers(0, 4, (R0, F), np.uint8),
                          index=False)
        assert eng.index is None
        pat = rng.integers(0, 4, P, np.uint8)
        res = eng.match(MatchQuery.exact(pat, reduction="threshold",
                                         threshold=THR, filter=True))
        assert res.plan.strategy == "scan"     # hint is moot without index

    def test_non_threshold_reductions_never_filter(self):
        eng = self.big_engine()
        pat = np.arange(32, dtype=np.uint8) % 4
        for red, kw in (("best", {}), ("topk", {"k": 3}), ("full", {})):
            cm = eng.compile(MatchQuery.exact(pat, reduction=red, **kw))
            assert cm.plan.strategy == "scan"

    def test_filter_hint_rejected_for_row_dense_reductions(self):
        with pytest.raises(ValueError, match="threshold"):
            MatchQuery.exact(np.zeros(4, np.uint8), filter=True)

    def test_rows_subset_never_filters(self):
        eng = self.big_engine()
        pat = np.arange(32, dtype=np.uint8) % 4
        cm = eng.compile(MatchQuery.exact(
            pat, reduction="threshold", threshold=32.0, rows=range(64)))
        assert cm.plan.strategy == "scan"

    def test_unprunable_wildcards_scan(self):
        """A pattern whose every q-gram spans a wildcard has no signature
        bits -- the filter cannot prune and must not be chosen."""
        eng = self.big_engine()
        masks = np.full(32, 0b1111, np.uint8)  # all-N pattern
        cm = eng.compile(MatchQuery.from_masks(
            masks, reduction="threshold", threshold=32.0, filter=True))
        assert cm.plan.strategy == "scan"

    def test_planner_plan_accepts_filter_ctx(self):
        from repro.match import FilterContext
        pl = Planner()
        ctx = FilterContext(sig_words=8, n_queries=1, prunable=True,
                            survivor_frac=1e-5)
        p = pl.plan(n_rows=100000, fragment_chars=256, pattern_chars=32,
                    predicate="exact", filter_ctx=ctx)
        assert p.strategy == "filter"
        assert p.est_seconds < pl.plan(
            n_rows=100000, fragment_chars=256, pattern_chars=32,
            predicate="exact").est_seconds


class TestServiceFilterRouting:
    def make_service(self, seed=40):
        rng = np.random.default_rng(seed)
        pat = rng.integers(0, 4, P, np.uint8)
        _, frags, eng = make_engine(seed=seed, planted=(4, 9), pat=pat)
        return rng, pat, eng, MatchService(eng)

    def test_filtered_launch_counted_and_identical(self):
        rng, pat, eng, svc = self.make_service()
        t = svc.submit(MatchQuery.exact(pat, reduction="threshold",
                                        threshold=THR, filter=True))
        svc.flush()
        want = eng.match(MatchQuery.exact(pat, reduction="threshold",
                                          threshold=THR, filter=False))
        np.testing.assert_array_equal(t.result.hits, want.hits)
        snap = svc.stats.snapshot()
        assert snap["n_filtered_launches"] == 1
        assert snap["filter_hit_rate"] == 1.0
        assert 0 < snap["avg_survivor_frac"] < 1

    def test_coalesced_threshold_group_filters_once(self):
        rng, pat, eng, svc = self.make_service(41)
        pats = [pat] + [rng.integers(0, 4, P, np.uint8) for _ in range(3)]
        tickets = [svc.submit(MatchQuery.exact(
            p, reduction="threshold", threshold=THR, filter=True))
            for p in pats]
        svc.flush()
        assert svc.stats.n_coalesced_launches == 1
        assert svc.stats.n_filtered_launches == 1   # union filter, 1 launch
        for t, p in zip(tickets, pats):
            want = eng.match(MatchQuery.exact(
                p, reduction="threshold", threshold=THR, filter=False))
            np.testing.assert_array_equal(t.result.hits, want.hits)

    def test_coalesced_group_of_16_is_one_filter_dispatch(self):
        rng, pat, eng, svc = self.make_service(44)
        pats = np.stack([pat] + [rng.integers(0, 4, P, np.uint8)
                                 for _ in range(15)])
        thrs = [THR - (i % 3) for i in range(16)]
        counters = eng.obs.metrics.counters
        tickets = [svc.submit(MatchQuery.exact(
            p, reduction="threshold", threshold=t, filter=True))
            for p, t in zip(pats, thrs)]
        svc.flush()
        assert svc.stats.n_coalesced_launches == 1
        assert counters["filter.dispatches"].value == 1
        assert counters["filter.patterns"].value == 16
        # The per-read reference union over the live rows' signatures.
        ops = build_query_filter((np.uint8(1) << pats).astype(np.uint8),
                                 thrs, eng.index.q, eng.index.n_bits)
        sigs, _ = row_signatures(eng.corpus.fragments, eng.index.q,
                                 eng.index.n_bits)
        want = np.zeros(len(sigs), np.int32)
        for q, slack in zip(ops.qsig_words, ops.slacks):
            want |= filter_qgram_ref(sigs, q[None, :], slack)
        for t in tickets:
            np.testing.assert_array_equal(t.result.survivor_rows,
                                          np.flatnonzero(want))
        # A lone query is one dispatch of one pattern.
        svc.submit(MatchQuery.exact(pats[2], reduction="threshold",
                                    threshold=THR, filter=True))
        svc.flush()
        assert counters["filter.dispatches"].value == 2
        assert counters["filter.patterns"].value == 17

    def test_per_tick_and_cache_stats(self):
        rng, pat, eng, svc = self.make_service(42)
        q = MatchQuery.exact(pat, reduction="threshold", threshold=THR)
        svc.submit(q)
        svc.tick()
        assert svc.stats.n_ticks == 1
        assert svc.stats.launches_last_tick == 1
        svc.submit(q)                          # result-cache hit
        svc.tick()
        snap = svc.stats.snapshot()
        assert snap["n_ticks"] == 2
        assert snap["launches_last_tick"] == 0
        assert snap["cache_hit_rate"] == 0.5
        assert snap["avg_launches_per_tick"] == 0.5

    def test_empty_tick_resets_last_tick_launches(self):
        rng, pat, eng, svc = self.make_service(43)
        svc.submit(MatchQuery.exact(pat))
        svc.tick()
        assert svc.stats.launches_last_tick == 1
        svc.tick()
        assert svc.stats.launches_last_tick == 0


class TestReserveShrink:
    def test_reserve_below_live_rows_raises(self):
        rng = np.random.default_rng(50)
        corpus = PackedCorpus(rng.integers(0, 4, (R0, F), np.uint8))
        with pytest.raises(ValueError) as ei:
            corpus.reserve(R0 - 5)
        msg = str(ei.value)
        assert f"{R0} live rows" in msg and str(R0 - 5) in msg

    def test_reserve_between_live_and_capacity_is_noop(self):
        rng = np.random.default_rng(51)
        corpus = PackedCorpus(rng.integers(0, 4, (R0, F), np.uint8),
                              capacity=4 * R0)
        corpus.reserve(2 * R0)                 # can't shrink; no-op
        assert corpus.capacity == 4 * R0
