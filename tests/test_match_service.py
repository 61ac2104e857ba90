"""Match service tests: coalescing correctness vs. per-query oracles,
cache hit semantics (including invalidation on corpus writes), pricing,
queue/ticket mechanics, stats.

The load-bearing property is that a caller can never tell whether their
query ran solo or was fused into a batched launch with strangers' queries:
every scattered result must be bit-identical to a direct
``MatchEngine.match`` call.
"""

import numpy as np
import pytest

from repro.match import MatchEngine, MatchService

R, F, P = 24, 96, 16


def make(seed=0, cache_size=256):
    rng = np.random.default_rng(seed)
    frags = rng.integers(0, 4, (R, F), np.uint8)
    eng = MatchEngine(frags)
    return rng, eng, MatchService(eng, cache_size=cache_size)


def assert_same_result(got, want):
    np.testing.assert_array_equal(got.best_locs, want.best_locs)
    np.testing.assert_array_equal(got.best_scores, want.best_scores)
    for f in ("scores", "topk_rows", "topk_scores", "hits"):
        a, b = getattr(got, f), getattr(want, f)
        assert (a is None) == (b is None), f
        if a is not None:
            np.testing.assert_array_equal(a, b, err_msg=f)


class TestCoalescingCorrectness:
    @pytest.mark.parametrize("reduction", ["best", "full"])
    def test_fused_equals_oracle(self, reduction):
        rng, eng, svc = make(1)
        pats = [rng.integers(0, 4, P, np.uint8) for _ in range(6)]
        tickets = [svc.submit(p, reduction=reduction) for p in pats]
        svc.flush()
        assert svc.stats.n_coalesced_launches == 1
        assert svc.stats.n_launches == 1
        for t, p in zip(tickets, pats):
            assert_same_result(t.result, eng.match(p, reduction=reduction))

    def test_fused_topk_per_query_k(self):
        rng, eng, svc = make(2)
        pats = [rng.integers(0, 4, P, np.uint8) for _ in range(5)]
        ks = [1, 3, 7, 2, 50]                     # includes k > R
        tickets = [svc.submit(p, reduction="topk", k=k)
                   for p, k in zip(pats, ks)]
        svc.flush()
        for t, p, k in zip(tickets, pats, ks):
            want = eng.match(p, reduction="topk", k=k)
            np.testing.assert_array_equal(t.result.topk_scores,
                                          want.topk_scores)
            assert t.result.topk_rows.shape == want.topk_rows.shape

    def test_fused_threshold_per_query_threshold(self):
        rng, eng, svc = make(3)
        pats = [rng.integers(0, 4, P, np.uint8) for _ in range(5)]
        thrs = [6, 8, 10, 7, 9]
        tickets = [svc.submit(p, reduction="threshold", threshold=t)
                   for p, t in zip(pats, thrs)]
        svc.flush()
        for t, p, thr in zip(tickets, pats, thrs):
            want = eng.match(p, reduction="threshold", threshold=thr)
            np.testing.assert_array_equal(t.result.hits, want.hits)

    def test_rows_subsets_do_not_cross_coalesce(self):
        """Different row subsets are incompatible groups; results still
        match the per-query oracle."""
        rng, eng, svc = make(4)
        pats = [rng.integers(0, 4, P, np.uint8) for _ in range(4)]
        subs = [None, [3, 1, 8], None, [3, 1, 8]]
        tickets = [svc.submit(p, rows=s) for p, s in zip(pats, subs)]
        svc.flush()
        assert svc.stats.n_launches == 2          # one group per subset
        for t, p, s in zip(tickets, pats, subs):
            assert_same_result(t.result, eng.match(p, rows=s))

    def test_empty_subset_through_service(self):
        rng, eng, svc = make(5)
        pat = rng.integers(0, 4, P, np.uint8)
        res = svc.match(pat, rows=np.array([], dtype=int))
        assert res.best_locs.shape == (0,)

    def test_mixed_pattern_lengths_grouped_separately(self):
        rng, eng, svc = make(6)
        p16 = [rng.integers(0, 4, 16, np.uint8) for _ in range(3)]
        p32 = [rng.integers(0, 4, 32, np.uint8) for _ in range(3)]
        ts = [svc.submit(p) for p in p16 + p32]
        svc.flush()
        assert svc.stats.n_launches == 2
        for t, p in zip(ts, p16 + p32):
            assert_same_result(t.result, eng.match(p))

    def test_two_dim_patterns_pass_through(self):
        rng, eng, svc = make(7)
        pats = rng.integers(0, 4, (4, P), np.uint8)
        res = svc.match(pats, mode="batched")
        assert_same_result(res, eng.match(pats, mode="batched"))

    def test_same_tick_duplicates_share_one_query(self):
        rng, eng, svc = make(8)
        pat = rng.integers(0, 4, P, np.uint8)
        other = rng.integers(0, 4, P, np.uint8)
        ts = [svc.submit(pat), svc.submit(other), svc.submit(pat)]
        svc.flush()
        assert svc.stats.n_launches == 1
        assert ts[0].result is ts[2].result       # deduped within the tick
        assert_same_result(ts[0].result, eng.match(pat))


class TestCacheSemantics:
    def test_cache_hit_on_repeat(self):
        rng, eng, svc = make(10)
        pat = rng.integers(0, 4, P, np.uint8)
        first = svc.match(pat)
        hit = svc.submit(pat)
        svc.tick()
        assert hit.cached and hit.result is first
        assert svc.stats.n_cache_hits == 1
        assert svc.stats.n_launches == 1          # no second launch

    def test_different_k_not_conflated(self):
        rng, eng, svc = make(11)
        pat = rng.integers(0, 4, P, np.uint8)
        a = svc.match(pat, reduction="topk", k=2)
        b = svc.match(pat, reduction="topk", k=5)
        assert a.topk_rows.shape == (2,) and b.topk_rows.shape == (5,)
        assert svc.stats.n_cache_hits == 0

    def test_set_rows_invalidates(self):
        rng, eng, svc = make(12)
        pat = rng.integers(0, 4, P, np.uint8)
        stale = svc.match(pat)
        gen = eng.corpus.generation
        eng.corpus.set_rows(0, rng.integers(0, 4, (R, F), np.uint8))
        assert eng.corpus.generation > gen
        fresh = svc.submit(pat)
        svc.tick()
        assert not fresh.cached
        assert_same_result(fresh.result, eng.match(pat))
        with pytest.raises(AssertionError):
            np.testing.assert_array_equal(fresh.result.best_scores,
                                          stale.best_scores)

    def test_lru_eviction(self):
        rng, eng, svc = make(13, cache_size=2)
        pats = [rng.integers(0, 4, P, np.uint8) for _ in range(3)]
        for p in pats:
            svc.match(p)                          # fills, evicts pats[0]
        svc.match(pats[0])
        assert svc.stats.n_cache_hits == 0
        svc.match(pats[0])                        # now resident
        assert svc.stats.n_cache_hits == 1


class TestPricingAndStats:
    def test_coalesced_launch_counted(self):
        rng, eng, svc = make(20)
        for p in [rng.integers(0, 4, P, np.uint8) for _ in range(8)]:
            svc.submit(p)
        svc.tick()
        s = svc.stats.snapshot()
        assert s["n_coalesced_launches"] == 1
        assert s["n_coalesced_queries"] == 8
        assert s["n_completed"] == 8
        assert s["avg_latency_s"] > 0 and s["qps"] > 0

    def test_singleton_group_runs_solo(self):
        rng, eng, svc = make(21)
        svc.match(rng.integers(0, 4, P, np.uint8))
        assert svc.stats.n_coalesced_launches == 0
        assert svc.stats.n_launches == 1

    def test_tick_returns_completed_count(self):
        rng, eng, svc = make(22)
        for p in [rng.integers(0, 4, P, np.uint8) for _ in range(3)]:
            svc.submit(p)
        assert svc.tick() == 3
        assert svc.tick() == 0

    def test_bad_request_does_not_poison_tick(self):
        """One tenant's malformed query fails its own ticket; everyone
        else's requests in the same tick still complete."""
        rng, eng, svc = make(24)
        good = svc.submit(rng.integers(0, 4, P, np.uint8))
        bad = svc.submit(np.zeros(F + 1, np.uint8))   # longer than fragment
        done = svc.tick()
        assert done == 2 and good.done and bad.done
        assert good.error is None and good.result is not None
        assert isinstance(bad.error, ValueError)
        with pytest.raises(ValueError, match="longer"):
            bad.wait()
        assert svc.stats.n_failed == 1

    def test_explicit_shared_mode_coalesces(self):
        """mode='shared' on a 1-D pattern is the default spelled out; it
        must coalesce and share cache entries with mode=None."""
        rng, eng, svc = make(25)
        pat = rng.integers(0, 4, P, np.uint8)
        other = rng.integers(0, 4, P, np.uint8)
        svc.submit(pat, mode="shared")
        svc.submit(other)
        svc.tick()
        assert svc.stats.n_coalesced_launches == 1
        hit = svc.submit(pat)
        svc.tick()
        assert hit.cached

    def test_submit_validates(self):
        rng, eng, svc = make(23)
        with pytest.raises(ValueError, match="unknown reduction"):
            svc.submit(np.zeros(P, np.uint8), reduction="nope")
        with pytest.raises(ValueError, match="requires a threshold"):
            svc.submit(np.zeros(P, np.uint8), reduction="threshold")


class TestMatchLauncher:
    """``repro.launch.serve --workload match`` fails loudly: the service
    isolates a failing group on its tickets, the launcher does not."""

    ARGV = ["--workload", "match", "--corpus-rows", "32",
            "--fragment-chars", "64", "--pattern-chars", "16",
            "--requests", "8", "--ingest-every", "4", "--tick-every", "4"]

    def _args(self):
        from repro.launch import serve
        return serve, serve.build_parser().parse_args(self.ARGV)

    def test_clean_run_passes(self, capsys):
        serve, args = self._args()
        serve.run_match_service(args)
        assert "served 8 exact match queries" in capsys.readouterr().out

    def test_failing_group_fails_launcher(self, monkeypatch):
        serve, args = self._args()

        def refuse(self, grp):
            raise RuntimeError("kernel refused by the compiler")
        monkeypatch.setattr(MatchService, "_run_group", refuse)
        with pytest.raises(RuntimeError, match="match queries failed") as ei:
            serve.run_match_service(args)
        assert "kernel refused" in str(ei.value.__cause__)
