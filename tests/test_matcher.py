"""Matcher (Algorithm 1) + scheduler tests."""

import numpy as np
import pytest

from repro.core import encoding
from repro.core.matcher import (Matcher, best_alignment, compile_alignment,
                                count_alignment_ops, plan_layout,
                                sliding_scores)
from repro.core.scheduler import (KmerIndex, expected_candidates,
                                  schedule_naive, schedule_oracular)


class TestEncoding:
    def test_dna_roundtrip(self):
        s = "ACGTACGTTTGGCCAA"
        assert encoding.decode_dna(encoding.encode_dna(s)) == s

    def test_bits_roundtrip(self):
        rng = np.random.default_rng(0)
        codes = rng.integers(0, 4, (3, 17), np.uint8)
        bits = encoding.codes_to_bits(codes)
        assert bits.shape == (3, 34)
        np.testing.assert_array_equal(encoding.bits_to_codes(bits), codes)

    def test_pack_unpack_u32(self):
        rng = np.random.default_rng(1)
        codes = rng.integers(0, 4, (5, 37), np.uint8)
        words = encoding.pack_codes_u32(codes)
        assert words.shape == (5, 3)  # ceil(37/16)
        np.testing.assert_array_equal(
            encoding.unpack_codes_u32(words, 37), codes)

    @pytest.mark.parametrize("bits,n", [(2, 1), (2, 16), (2, 37),
                                        (2, 256), (1, 33)])
    def test_pack_u32_matches_definition(self, bits, n):
        """Char i sits at bits [i*bits, (i+1)*bits) of word i // (32/bits)
        (the strided-pass packer against the per-character definition)."""
        rng = np.random.default_rng(n)
        codes = rng.integers(0, 1 << bits, (4, n), np.uint8)
        cpw = 32 // bits
        want = np.zeros((4, -(-n // cpw)), np.uint64)
        for r in range(4):
            for i in range(n):
                want[r, i // cpw] |= int(codes[r, i]) << ((i % cpw) * bits)
        np.testing.assert_array_equal(encoding.pack_codes_u32(codes, bits),
                                      want.astype(np.uint32))

    def test_one_hot_form_matches_definition(self):
        """Column f*4 + c is 1 iff fragment char f is code c; pad is 0."""
        from repro.match.corpus import _one_hot_flat
        rng = np.random.default_rng(3)
        frags = rng.integers(0, 4, (70_000, 9), np.uint8)  # > one block
        got = _one_hot_flat(frags, 40)
        want = np.zeros((70_000, 40), np.float32)
        for f in range(9):
            want[np.arange(70_000), f * 4 + frags[:, f]] = 1
        np.testing.assert_array_equal(got, want)

    def test_fold_reference_overlap(self):
        """Adjacent fragments overlap by P-1 so no alignment is lost."""
        rng = np.random.default_rng(2)
        ref = rng.integers(0, 4, 1000, np.uint8)
        P = 10
        frags = encoding.fold_reference(ref, fragment_len=100, pattern_len=P)
        # Every length-P window of ref appears in some fragment row.
        step = 100 - (P - 1)
        for loc in range(len(ref) - P + 1):
            r = min(loc // step, frags.shape[0] - 1)
            # window must be fully inside row r or row loc//step
            found = False
            for row in range(frags.shape[0]):
                start = row * step
                if start <= loc and loc + P <= start + 100:
                    np.testing.assert_array_equal(
                        frags[row, loc - start: loc - start + P],
                        ref[loc: loc + P])
                    found = True
                    break
            assert found, loc


class TestMatcher:
    def test_scores_match_oracle(self):
        rng = np.random.default_rng(0)
        frags = rng.integers(0, 4, (16, 32), np.uint8)
        pat = rng.integers(0, 4, 8, np.uint8)
        m = Matcher(frags, pattern_chars=8)
        m.load_pattern(pat)
        np.testing.assert_array_equal(m.run(), sliding_scores(frags, pat))

    def test_scores_match_oracle_opt_schedule(self):
        """Gang-preset schedule is functionally identical (paper Sec. 5.1)."""
        rng = np.random.default_rng(4)
        frags = rng.integers(0, 4, (8, 20), np.uint8)
        pat = rng.integers(0, 4, 5, np.uint8)
        m_plain = Matcher(frags, pattern_chars=5, opt=False)
        m_opt = Matcher(frags, pattern_chars=5, opt=True)
        m_plain.load_pattern(pat)
        m_opt.load_pattern(pat)
        np.testing.assert_array_equal(m_plain.run(), m_opt.run())

    def test_per_row_patterns(self):
        rng = np.random.default_rng(1)
        frags = rng.integers(0, 4, (6, 24), np.uint8)
        pats = rng.integers(0, 4, (6, 6), np.uint8)
        m = Matcher(frags, pattern_chars=6)
        m.load_patterns_per_row(pats)
        np.testing.assert_array_equal(m.run(), sliding_scores(frags, pats))

    def test_planted_exact_match_wins(self):
        rng = np.random.default_rng(2)
        frags = rng.integers(0, 4, (4, 40), np.uint8)
        pat = rng.integers(0, 4, 10, np.uint8)
        frags[2, 7:17] = pat
        m = Matcher(frags, pattern_chars=10)
        m.load_pattern(pat)
        locs, scores = best_alignment(m.run())
        assert scores[2] == 10 and locs[2] == 7

    def test_partial_run_locs(self):
        rng = np.random.default_rng(3)
        frags = rng.integers(0, 4, (4, 20), np.uint8)
        pat = rng.integers(0, 4, 5, np.uint8)
        m = Matcher(frags, pattern_chars=5)
        m.load_pattern(pat)
        sub = m.run(range(3, 7))
        full = sliding_scores(frags, pat)
        np.testing.assert_array_equal(sub, full[:, 3:7])

    def test_layout_fits_2k_row(self):
        """Paper geometry: 100-char pattern in a ~2.4K-cell row leaves a
        ~1000-char fragment (Sec. 4 case study)."""
        layout = plan_layout(2400, 100, scratch_budget=128)
        assert 900 <= layout.fragment_chars <= 1050
        assert layout.score_bits == 7

    def test_census_against_paper(self):
        """Per-alignment op census: 7 logic steps per char in Phase 1 + ~188
        FAs in Phase 2 (paper Sec. 3.2)."""
        c = count_alignment_ops(100)
        assert c["NOR"] == 300 and c["TH"] == 200    # 3+2 per char
        assert 180 <= c["FA_COUNT"] <= 200
        assert c["SCORE_BITS"] == 7

    def test_compile_alignment_bounds(self):
        layout = plan_layout(512, 10)
        with pytest.raises(ValueError):
            compile_alignment(layout, layout.n_alignments)


class TestScheduler:
    def test_naive_pass_count(self):
        s = schedule_naive(n_rows=8, n_patterns=5)
        assert s.n_passes == 5
        assert all(len(p) == 8 for p in s.passes)

    def test_oracular_fewer_passes_than_naive(self):
        rng = np.random.default_rng(0)
        frags = rng.integers(0, 4, (32, 64), np.uint8)
        pats = np.stack([
            frags[i % 32, 5:25] for i in range(64)])  # planted patterns
        s = schedule_oracular(frags, pats, k=8)
        assert s.n_passes < 64  # naive would need 64 passes

    def test_oracular_schedules_every_pattern_at_its_home_row(self):
        rng = np.random.default_rng(1)
        frags = rng.integers(0, 4, (16, 48), np.uint8)
        pats = np.stack([frags[i, 10:30] for i in range(16)])
        s = schedule_oracular(frags, pats, k=8)
        # every pattern must be scheduled on its true home row in some pass
        for p in range(16):
            assert any(assign.get(p) == p for assign in s.passes), p

    def test_kmer_index_candidates(self):
        frags = np.array([[0, 1, 2, 3, 0, 1], [3, 2, 1, 0, 3, 2]], np.uint8)
        idx = KmerIndex(frags, k=3)
        cand = idx.candidate_rows(np.array([0, 1, 2], np.uint8))
        assert 0 in cand.tolist()

    def test_expected_candidates_paper_scale(self):
        """At paper scale (3G ref, 100-char patterns, k=15) the analytic
        model predicts ~300 candidate rows -> ~300 Oracular passes for 3M
        patterns on 3M rows, i.e. the paper's ~10^4x Naive/Oracular gap."""
        c = expected_candidates(3e9, 100, k=15)
        assert 200 < c < 450

    def test_schedule_replication_consistency(self):
        rng = np.random.default_rng(5)
        frags = rng.integers(0, 4, (8, 40), np.uint8)
        pats = rng.integers(0, 4, (12, 12), np.uint8)
        s = schedule_oracular(frags, pats, k=4)
        assert s.replication == pytest.approx(
            sum(len(p) for p in s.passes) / 12)
