#!/usr/bin/env python3
"""Run the match stack's main path on a TPU and check every answer.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # a corpus sharded over four chips

One chip: a 2^20 x 256-character DNA corpus (about 268 Mbp, the scale of
one human chromosome) generated from a seed, with 32-mer needles planted
at known rows, lives on the device in a ``MatchEngine``.  A
``MatchService`` over it answers exact best and top-k queries, exact
threshold lookups (routed through the q-gram filter), IUPAC / N-wildcard
queries, a coalesced tick of 64 shared-mode queries and an ingest of 1024
new rows; a batched wildcard query runs the MXU kernel; a ``PatternBank``
of 1024 standing patterns scans four ingest ticks of 64 documents.  Every
result is compared with the NumPy oracle (``core.matcher``) on a seeded
sample of rows and every planted needle with its known position.

Four chips: a 2^22-row corpus sharded row-wise over a four-device mesh;
threshold (scan and filtered), top-k, best and IUPAC queries, then
``append_rows``, ``tombstone`` and ``compact``, each bit-identical to an
unsharded engine over the same corpus in the same process.

Earlier lines report plans, phase wall times (compilation apart) and peak
device memory; none of these times is a benchmark metric.  The last line
is one JSON object, ``{"ok": true, "device": {...}}``, printed only when
every phase passed on a TPU.  Without a TPU, or outside a checkout of the
repository, the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import json
import sys
import time
from pathlib import Path

import numpy as np

SEED = 20181221
F, P = 256, 32
SAMPLE_ROWS = 4096


class SmokeFailure(Exception):
    """A phase produced a wrong answer or an unexpected plan."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- accounting ---------------------------------------------------------------

class Phases:
    """Wall time per phase, with XLA backend compilation reported apart.

    Only the backend-compile event is summed: tracing and lowering events
    nest inside one another, so adding them up would overstate the time.
    A program read back from the persistent cache still counts as one
    backend compile (its time is the cache read); ``cache_hits`` says how
    many of them were.
    """

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.n_compiles = 0
        self.cache_hits = 0
        self.programs: collections.Counter = collections.Counter()
        self.program_s: collections.Counter = collections.Counter()
        self.by_phase: dict = {}         # phase name -> Counter of programs

        def on_duration(event, duration, fun_name="?", **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += duration
                self.n_compiles += 1
                self.programs[fun_name] += 1
                self.program_s[fun_name] += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    @contextlib.contextmanager
    def __call__(self, name: str):
        c0, n0, h0 = self.compile_s, self.n_compiles, self.cache_hits
        p0, s0 = self.programs.copy(), self.program_s.copy()
        t0 = time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        new = self.by_phase[name] = self.programs - p0
        secs = self.program_s - s0
        top = ", ".join(f"{k} x{new[k]} {v:.3f} s"
                        for k, v in secs.most_common(4))
        print(f"phase {name}: wall {wall:.3f} s, of which backend "
              f"compilation {self.compile_s - c0:.3f} s over "
              f"{self.n_compiles - n0} programs ({self.cache_hits - h0} "
              f"from the cache){'; slowest: ' + top if top else ''}",
              flush=True)


def assert_compiled(*engines) -> None:
    """The kernels must compile for the chip, never run interpreted."""
    from repro.kernels import default_interpret
    check(default_interpret() is False
          and all(e.interpret is False for e in engines),
          "kernels would run in the Pallas interpreter, not on the chip")


def note_plan(label: str, res) -> None:
    p = res.plan
    print(f"plan {label}: backend={p.backend} predicate={p.predicate} "
          f"mode={p.mode} strategy={p.strategy} rows={p.n_rows} "
          f"chunk_rows={p.chunk_rows} cost_source={p.cost_source}",
          flush=True)


def expect_plan(label: str, res, backend: str, predicate: str,
                strategy: str = None) -> None:
    note_plan(label, res)
    p = res.plan
    check(p.backend == backend and p.predicate == predicate,
          f"{label}: plan ran {p.backend}/{p.predicate}, expected "
          f"{backend}/{predicate}")
    check(strategy is None or p.strategy == strategy,
          f"{label}: plan strategy {p.strategy}, expected {strategy}")


def served(label: str, tickets):
    """Results of served tickets; any error or missing result fails."""
    out = []
    for i, t in enumerate(tickets):
        check(t.done, f"{label}: ticket {i} not done")
        if t.error is not None:
            raise SmokeFailure(f"{label}: ticket {i} failed: "
                               f"{t.error!r}") from t.error
        check(t.result is not None, f"{label}: ticket {i} has no result")
        out.append(t.result)
    return out


# -- data and oracles ---------------------------------------------------------

def make_corpus(rng, n_rows: int, needles: np.ndarray, copies: int):
    """Random rows with every needle planted ``copies`` times at known
    (row, loc); returns (fragments, {needle: sorted [(row, loc)]})."""
    frags = rng.integers(0, 4, (n_rows, F), np.uint8)
    rows = rng.choice(n_rows, len(needles) * copies, replace=False)
    planted = {}
    for i, needle in enumerate(needles):
        sites = []
        for r in rows[i * copies:(i + 1) * copies]:
            loc = int(rng.integers(0, F - P + 1))
            frags[r, loc:loc + P] = needle
            sites.append((int(r), loc))
        planted[i] = sorted(sites)
    return frags, planted


def oracle_best(frags, masks):
    """(locs, scores) of the best alignment per row (first max wins)."""
    from repro.core.matcher import sliding_scores_masks
    sc = sliding_scores_masks(frags, masks)
    return sc.argmax(1), sc.max(1)


def exact_masks(codes):
    return (np.uint8(1) << np.asarray(codes, np.uint8)).astype(np.uint8)


def to_iupac(needle, wild, ambiguous) -> str:
    """The needle as an IUPAC string: N at ``wild``, R/Y at ``ambiguous``."""
    s = ["ACGT"[c] for c in needle]
    for i in wild:
        s[i] = "N"
    for i in ambiguous:
        s[i] = "R" if s[i] in "AG" else "Y"
    return "".join(s)


def check_best_on_sample(label, res, frags, sample, masks, col=None):
    locs, scores = oracle_best(frags[sample], masks)
    got_l = res.best_locs[sample] if col is None else \
        res.best_locs[sample, col]
    got_s = res.best_scores[sample] if col is None else \
        res.best_scores[sample, col]
    check(np.array_equal(got_s, scores) and np.array_equal(got_l, locs),
          f"{label}: best alignment differs from the NumPy oracle on "
          f"{int((got_s != scores).sum())} of {len(sample)} sampled rows")


def check_hits(label, hits, want_sites):
    got = sorted((int(r), int(l)) for r, l, s in hits if s >= P)
    check(got == sorted(want_sites),
          f"{label}: hits {got[:8]} != planted {sorted(want_sites)[:8]}")


# -- one chip -----------------------------------------------------------------

def run_one_chip(phase: Phases, n_rows: int = 1 << 20,
                 mxu_fallback_rows: int = 1 << 14) -> None:
    from repro.core import encoding
    from repro.match import (MatchEngine, MatchQuery, MatchService,
                             PackedCorpus, PatternBank, load_cost_source)
    from repro.core.matcher import sliding_scores

    rng = np.random.default_rng(SEED)
    needles = rng.integers(0, 4, (8, P), np.uint8)
    with phase("setup"):
        frags, planted = make_corpus(rng, n_rows, needles, copies=8)
        sample = np.sort(rng.choice(n_rows, min(SAMPLE_ROWS, n_rows),
                                    replace=False))
        cost = load_cost_source()
        print("cost source: " + (cost.tag if cost is not None else
                                 "static (no calibration table for this "
                                 "device)"), flush=True)
        engine = MatchEngine(PackedCorpus(frags, capacity=n_rows + 1024),
                             cost_source=cost)
        svc = MatchService(engine)
        print(f"engine: {engine!r}", flush=True)
        assert_compiled(engine)

    with phase("exact best + top-k"):
        tb = svc.submit(MatchQuery.exact(needles[0]))
        tk = svc.submit(MatchQuery.exact(needles[0], reduction="topk", k=5))
        svc.tick()
        best, top = served("exact", [tb, tk])
        expect_plan("exact best", best, "swar", "exact")
        expect_plan("exact topk", top, "swar", "exact")
        check_best_on_sample("exact best", best, frags, sample,
                             exact_masks(needles[0]))
        for r, loc in planted[0]:
            check(best.best_scores[r] == P and best.best_locs[r] == loc,
                  f"exact best: planted row {r} not found at {loc}")
        want_top = [r for r, _ in planted[0]][:5]
        check(top.topk_rows.tolist() == want_top
              and top.topk_scores.tolist() == [P] * 5,
              f"exact topk: {top.topk_rows.tolist()} != {want_top}")

    with phase("filtered threshold lookups"):
        tickets = [svc.submit(MatchQuery.exact(n, reduction="threshold",
                                               threshold=P))
                   for n in needles]
        svc.tick()
        for i, res in enumerate(served("threshold", tickets)):
            expect_plan(f"threshold needle {i}", res, "swar", "exact",
                        strategy="filter")
            check_hits(f"threshold needle {i}", res.hits, planted[i])
        print(f"filter survivors: {res.survivor_frac:.3g} of rows",
              flush=True)

    with phase("IUPAC / N-wildcard"):
        pattern = to_iupac(needles[1], wild=(2, 17, 30), ambiguous=(9,))
        tt = svc.submit(MatchQuery.iupac(pattern, reduction="threshold",
                                         threshold=P))
        tb = svc.submit(MatchQuery.iupac(pattern))
        svc.tick()
        thr, best = served("iupac", [tt, tb])
        expect_plan("iupac threshold", thr, "swar", "accept")
        expect_plan("iupac best", best, "swar", "accept")
        check_hits("iupac threshold", thr.hits, planted[1])
        check_best_on_sample("iupac best", best, frags, sample,
                             encoding.encode_iupac(pattern))

    with phase("coalesced tick of 64 queries"):
        # Needles 1..7 plus random patterns: none is in the result cache
        # (needle 0's best query is), so all 64 reach the fused launch.
        pats = np.concatenate([needles[1:], rng.integers(0, 4, (57, P),
                                                         np.uint8)])
        before = svc.stats.n_coalesced_launches
        tickets = [svc.submit(MatchQuery.exact(p)) for p in pats]
        svc.tick()
        results = served("coalesced", tickets)
        check(svc.stats.n_coalesced_launches == before + 1,
              "coalesced: the 64 queries did not fuse into one launch")
        expect_plan("coalesced", results[0], "swar", "exact")
        check(results[0].plan.mode == "batched"
              and results[0].plan.n_patterns == 64,
              f"coalesced: plan mode {results[0].plan.mode} "
              f"x{results[0].plan.n_patterns}")
        sub = frags[sample]
        for q, res in enumerate(results):
            sc = sliding_scores(sub, pats[q])
            check(np.array_equal(res.best_scores[sample], sc.max(1))
                  and np.array_equal(res.best_locs[sample], sc.argmax(1)),
                  f"coalesced query {q}: differs from the NumPy oracle")
        for i in range(1, len(needles)):
            for r, loc in planted[i]:
                check(results[i - 1].best_locs[r] == loc
                      and results[i - 1].best_scores[r] == P,
                      f"coalesced needle {i}: row {r} not found at {loc}")

    with phase("ingest 1024 rows"):
        new = rng.integers(0, 4, (1024, F), np.uint8)
        new_sites = []
        for d in (3, 400, 777, 1023):
            loc = int(rng.integers(0, F - P + 1))
            new[d, loc:loc + P] = needles[2]
            new_sites.append((n_rows + d, loc))
        ti = svc.ingest(new)
        tq = svc.submit(MatchQuery.exact(needles[2], reduction="threshold",
                                         threshold=P, filter=False))
        svc.tick()
        (res,) = served("ingest", [tq])
        check(ti.done and ti.start == n_rows,
              f"ingest: rows landed at {ti.start}, expected {n_rows}")
        expect_plan("after ingest", res, "swar", "exact", strategy="scan")
        check_hits("after ingest", res.hits, planted[2] + new_sites)

    with phase("MXU batched wildcard"):
        masks = np.stack([exact_masks(p) for p in pats[:128]]
                         + [exact_masks(p) for p in
                            rng.integers(0, 4, (128 - len(pats), P),
                                         np.uint8)])
        masks[:, [5, 21]] = 0b1111                 # N wildcards
        query = MatchQuery.from_masks(masks, mode="batched")
        plan = engine.compile(query).plan
        if plan.backend == "mxu":
            print(f"MXU: the planner picked mxu for a batched wildcard "
                  f"query of {masks.shape[0]} patterns over all "
                  f"{engine.corpus.n_rows} rows ({plan.reason})", flush=True)
            mx_engine, mx_frags, mx_sample = engine, frags, sample
        else:
            print(f"MXU: the planner picked {plan.backend} at full size "
                  f"({plan.reason}); running backend='mxu' on a second "
                  f"engine of {mxu_fallback_rows} rows", flush=True)
            mx_frags = frags[:mxu_fallback_rows]
            mx_engine = MatchEngine(mx_frags, cost_source=cost)
            mx_sample = np.arange(mxu_fallback_rows)
            query = MatchQuery.from_masks(masks, mode="batched",
                                          backend="mxu")
        res = mx_engine.match(query)
        expect_plan("mxu", res, "mxu", "accept")
        for q in range(masks.shape[0]):
            check_best_on_sample(f"mxu column {q}", res, mx_frags,
                                 mx_sample, masks[q], col=q)
        for i in range(1, len(needles)):
            for r, loc in planted[i]:
                if r < mx_frags.shape[0]:
                    check(res.best_scores[r, i - 1] == P
                          and res.best_locs[r, i - 1] == loc,
                          f"mxu needle {i}: row {r} not found at {loc}")

    with phase("standing bank over 4 ingest ticks"):
        run_stream(rng, MatchEngine, MatchService, PackedCorpus, PatternBank)

    stats = engine.corpus.swar_pack_count, engine.corpus.onehot_pack_count
    print(f"resident packs (swar, onehot): {stats}; service "
          f"launches={svc.stats.n_launches} "
          f"coalesced={svc.stats.n_coalesced_launches} "
          f"filtered={svc.stats.n_filtered_launches}", flush=True)


def run_stream(rng, MatchEngine, MatchService, PackedCorpus, PatternBank,
               n_patterns: int = 1024, ticks: int = 4, docs: int = 64):
    """Standing bank: every planted hit found, one bank launch per tick."""
    from numpy.lib.stride_tricks import sliding_window_view

    pats = rng.integers(0, 4, (n_patterns, P), np.uint8)
    bank = PatternBank(F, P, capacity=n_patterns, filter=True)
    pids = [bank.register(p, threshold=P) for p in pats]
    corpus = PackedCorpus(rng.integers(0, 4, (1024, F), np.uint8))
    svc = MatchService(MatchEngine(corpus), bank=bank, window_rows=2048)
    weights = np.uint64(4) ** np.arange(P, dtype=np.uint64)
    key_of = {int(k): pid for k, pid in
              zip((pats.astype(np.uint64) * weights).sum(1), pids)}
    n_planted = 0
    for tick in range(ticks):
        batch = rng.integers(0, 4, (docs, F), np.uint8)
        for d in range(0, docs, 4):
            j = int(rng.integers(0, n_patterns))
            loc = int(rng.integers(0, F - P + 1))
            batch[d, loc:loc + P] = pats[j]
            n_planted += 1
        # Oracle: every exact occurrence of any bank pattern in the batch.
        win = sliding_window_view(batch, P, axis=1).astype(np.uint64)
        keys = (win * weights).sum(-1)
        want = sorted((d, l, key_of[int(k)])
                      for (d, l), k in np.ndenumerate(keys)
                      if int(k) in key_of)
        launches = bank.n_bank_launches, bank.n_prefilter_launches
        ticket = svc.ingest(batch)
        svc.tick()
        check(ticket.done, f"stream tick {tick}: ingest not applied")
        hits = ticket.bank_ticket.hits
        got = sorted((int(d), int(l), int(p)) for d, l, p, _ in hits)
        check(got == want, f"stream tick {tick}: bank hits {got[:6]} != "
              f"oracle {want[:6]}")
        check(bank.n_bank_launches - launches[0] == 1,
              f"stream tick {tick}: {bank.n_bank_launches - launches[0]} "
              "bank launches, expected exactly one")
        check(bank.n_prefilter_launches - launches[1] == 1,
              f"stream tick {tick}: the bank prefilter did not run")
    print(f"stream: {ticks} ticks x {docs} docs against {n_patterns} "
          f"standing patterns, {n_planted} planted hits all found, "
          f"1 bank launch per tick, prefilter survivors "
          f"{bank.last_survivor_frac:.3g}", flush=True)


# -- four chips ---------------------------------------------------------------

def snapshot(res) -> dict:
    out = {"best_locs": res.best_locs, "best_scores": res.best_scores,
           "backend": res.plan.backend, "strategy": res.plan.strategy}
    for f in ("hits", "topk_rows", "topk_scores", "survivor_rows"):
        if getattr(res, f) is not None:
            out[f] = getattr(res, f)
    return out


def run_four_chips(phase: Phases, n_rows: int = 1 << 22,
                   chunk_rows: int = 1 << 20) -> None:
    """Sharded vs unsharded engine over one corpus, stage by stage.

    Both engines stream the same pinned ``chunk_rows`` and the corpus
    reserves exactly one more chunk of capacity, so every scan runs
    whole chunks of one shape -- before and after the append, the
    tombstones and the compaction -- and each engine compiles each
    program once.  The planner's own chunk choice differs between the two
    engines (its memory budget is per device) and would leave a tail
    chunk of another shape in each.
    """
    import jax

    from repro.launch.mesh import make_row_mesh
    from repro.match import MatchEngine, MatchQuery, PackedCorpus

    check(len(jax.devices()) >= 4,
          f"--chips 4 needs four devices, found {len(jax.devices())}")
    check(n_rows % chunk_rows == 0, "n_rows must be whole chunks")
    rng = np.random.default_rng(SEED + 4)
    needles = rng.integers(0, 4, (2, P), np.uint8)
    with phase("setup (2 engines)"):
        frags, planted = make_corpus(rng, n_rows, needles, copies=8)
        cap = n_rows + chunk_rows
        single = MatchEngine(PackedCorpus(frags, capacity=cap),
                             record_runtimes=False)
        sharded = MatchEngine(PackedCorpus(frags, capacity=cap),
                              mesh=make_row_mesh(4), record_runtimes=False)
        print(f"engines: {single!r} | {sharded!r}; chunk_rows={chunk_rows}",
              flush=True)
        check(sharded.n_shards == 4, f"sharded engine has "
              f"{sharded.n_shards} row shards, expected 4")
        assert_compiled(single, sharded)

    iupac = to_iupac(needles[0], wild=(2, 17), ambiguous=(9,))
    spec = dict(chunk_rows=chunk_rows)
    queries = {
        "threshold_scan": MatchQuery.exact(
            needles[0], reduction="threshold", threshold=P, filter=False,
            **spec),
        "threshold_filtered": MatchQuery.exact(
            needles[0], reduction="threshold", threshold=P, filter=True,
            **spec),
        # Pinned to the scan: the filter routing is priced per shard, so
        # it may differ between the two engines (and with it which rows
        # best_locs covers); threshold_filtered covers the filter.
        "iupac": MatchQuery.iupac(iupac, reduction="threshold",
                                  threshold=P, filter=False, **spec),
        "topk": MatchQuery.exact(needles[0], reduction="topk", k=9, **spec),
        "best": MatchQuery.exact(needles[1], **spec),
    }

    def compare(stage: str, name: str) -> dict:
        a = single.match(queries[name])
        b = sharded.match(queries[name])
        note_plan(f"{stage} single", a)
        note_plan(f"{stage} sharded", b)
        check(b.merge_path == "device" and b.n_shards == 4,
              f"{stage}: merged on {b.merge_path} over {b.n_shards} shards")
        sa, sb = snapshot(a), snapshot(b)
        check(sa.keys() == sb.keys(), f"{stage}: result fields differ")
        for k in sa:
            same = (sa[k] == sb[k] if isinstance(sa[k], str)
                    else np.array_equal(sa[k], sb[k]))
            check(same, f"{stage}: {k} differs between the sharded and "
                        "the unsharded engine")
        print(f"stage {stage}: sharded == unsharded "
              f"({', '.join(sorted(sa))})", flush=True)
        return sa

    with phase("queries"):
        snaps = {name: compare(name, name) for name in queries}
        for name in ("threshold_scan", "threshold_filtered", "iupac"):
            check_hits(name, snaps[name]["hits"], planted[0])
        check(snaps["threshold_filtered"]["strategy"] == "filter",
              "threshold_filtered: the q-gram filter did not run")

    with phase("append_rows + tombstone + compact"):
        # Three more copies of needle 0 in the appended block; two of them
        # die.  Compaction re-splices only the rows from the first dead
        # one on (about a thousand here), and the last copy's id shifts
        # down by two.
        extra = rng.integers(0, 4, (1024, F), np.uint8)
        new_sites = [(40, 20), (600, 100), (1000, 7)]
        for r, loc in new_sites:
            extra[r, loc:loc + P] = needles[0]
        new_sites = [(n_rows + r, loc) for r, loc in new_sites]
        for eng in (single, sharded):
            eng.corpus.append_rows(extra)
        res = compare("threshold_after_append", "threshold_scan")
        check_hits("after append", res["hits"], planted[0] + new_sites)
        compare("topk_after_append", "topk")
        dead = [r for r, _ in new_sites[:2]]
        for eng in (single, sharded):
            eng.corpus.tombstone(dead)
        res = compare("threshold_after_tombstone", "threshold_scan")
        check_hits("after tombstone", res["hits"],
                   planted[0] + new_sites[2:])
        for eng in (single, sharded):
            eng.corpus.compact()
        res = compare("threshold_after_compact", "threshold_scan")
        compare("best_after_compact", "best")
        # Zero false negatives: every live planted copy, ids shifted down
        # past the two compacted rows.
        want = planted[0] + [(r - len(dead), loc) for r, loc in new_sites[2:]]
        check_hits("after compact", res["hits"], want)

    form = sharded.corpus.swar_words(0)
    devs = {s.device for s in form.addressable_shards}
    rows = {s.data.shape[0] for s in form.addressable_shards}
    print(f"sharded corpus form {form.shape} on {len(devs)} devices, "
          f"{rows} rows each", flush=True)
    check(len(devs) == 4 and len(rows) == 1,
          f"sharded corpus sits on {len(devs)} devices, expected 4")


# -- entry point --------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded four-chip phase")
    args = ap.parse_args()

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro" / "match").is_dir():
        print(f"chip_smoke: {src} holds no repro package; run from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    import jax

    from repro.launch.cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    devices = jax.devices()
    dev = devices[0]
    print(f"devices: {len(devices)} x {dev.platform} {dev.device_kind!r}",
          flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend is {dev.platform}); "
              "this script never falls back to the CPU or the Pallas "
              "interpreter", file=sys.stderr)
        return 1

    phase = Phases()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips(phase)
        else:
            run_one_chip(phase)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"peak device memory: "
          f"{'not reported' if peak is None else f'{peak} bytes'}; total "
          f"wall {time.perf_counter() - t0:.3f} s, backend compilation "
          f"{phase.compile_s:.3f} s over {phase.n_compiles} programs "
          f"({phase.cache_hits} from the cache)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
