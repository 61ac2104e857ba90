"""Read mapping: reads against a chromosome held resident on the chip,
folded into overlapping rows (``"kind": "readmap"``).

Data: a uniform genome of the configuration's ``genome_bp`` from the seed,
folded into rows of ``fragment_chars`` every ``row_stride`` chars; reads
of ``pattern_chars`` drawn from it with the mix's errors (``bench/gen.py``).

Client: a closed loop.  One mapper submits a batch of reads, ticks the
service until every ticket is done, and submits the next.  The window
closes at the first batch completion after ``--seconds``; its length runs
from the window's start to that completion.  The loop keeps a seeded
reservoir sample of the window's answers for the reference check, and a
record of what each batch launched.  ``queries_per_s`` is the reads
completed over that length.

Check: the reference (``bench/reference.py``) runs once the window has
closed and the program's state is freed, over the sampled requests'
patterns in one pass over the corpus.  Every number compared is a count
of things that differ from the reference, and every limit is 0: the
configuration states exact answers.  Each appears only where the cell has
something to compare:

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

Control: the reference's own answers for the same sampled reads,
computed with scores rounded through float8, as if the timed path had
produced them.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, Iterator, List, Optional

import numpy as np

from bench import drive, gen, reference
from bench.spec import SpecError

SPANS = frozenset({"bench.window", "bench.submit"})


@dataclasses.dataclass
class Sample:
    """One answer kept for the reference check."""

    masks: np.ndarray
    spec: dict                       # reduction, k or threshold
    answer: Dict[str, np.ndarray]
    cached: bool = False


@dataclasses.dataclass
class Deployment:
    """A cell after set-up: the deployment, warmed, and its traffic."""

    stack: drive.Stack
    rows: np.ndarray
    reads: Iterator[gen.Read]        # the window's read stream
    traffic: dict
    sample: drive.Reservoir
    note: str


@dataclasses.dataclass
class Held:
    """What the check needs once the program's state is freed."""

    rows: np.ndarray
    samples: List[Sample]
    seen: int


# -- the kind's contract (``bench/spec.py``) ---------------------------------

def set_up(cs: dict, seed: int, devices, trace: bool) -> Deployment:
    """Corpus and reads from the seed, the deployment on ``devices``, and
    warm-up of the cell's own shapes (``warm_up``)."""
    config, traffic = cs["config"], cs["traffic"]
    t0 = time.perf_counter()
    F, stride = config["fragment_chars"], config["row_stride"]
    P, G = config["pattern_chars"], config["genome_bp"]
    g = gen.genome(seed, G, F, stride)
    rows = gen.fold(g, G, F, stride)
    t_data = time.perf_counter()
    stack = drive.build_stack(config, rows, devices, trace)
    t_deploy = time.perf_counter()
    warm = warm_up(stack, gen.read_stream(seed, g, G, P, traffic,
                                          gen.WARMUP), traffic)
    note = (f"data {t_data - t0:.3f} s, deployment {t_deploy - t_data:.3f} "
            f"s, warm-up {time.perf_counter() - t_deploy:.3f} s {warm}; "
            f"{rows.shape[0]} rows x {F} chars on {stack.engine.n_shards} "
            "shard(s)")
    return Deployment(stack, rows,
                      gen.read_stream(seed, g, G, P, traffic, gen.READS),
                      traffic, drive.Reservoir(traffic["check_sample"],
                                               gen.rng(seed, gen.SAMPLE)),
                      note)


def window(dep: Deployment, seconds: float, trace: bool) -> drive.Window:
    return closed_loop(dep.stack, dep.reads, dep.traffic, seconds,
                       dep.sample, trace)


def metric(name: str, win: drive.Window) -> float:
    if name == "queries_per_s":
        return win.completed / win.seconds
    raise SpecError(f"no measurement for {name!r}")


def release(dep: Deployment) -> Held:
    return Held(dep.rows, dep.sample.items, dep.sample.seen)


def check(held: Held, win: drive.Window) -> tuple:
    ref = reference_answers(held.samples, held.rows)
    cached = sum(s.cached for s in held.samples)
    return (numbers(win, held.samples, ref),
            f"{len(held.samples)} sampled answers of {held.seen} ({cached} "
            "from the result cache)")


def control(held: Held, win: drive.Window) -> Dict[str, dict]:
    ref = reference_answers(held.samples, held.rows)
    return numbers(win, control_samples(held.samples, held.rows), ref)


# -- closed loop -------------------------------------------------------------

def answer_of(res, spec: dict, keep_rows: bool) -> Dict[str, np.ndarray]:
    out = {}
    if spec["reduction"] == "topk":
        out["topk_rows"], out["topk_scores"] = res.topk_rows, res.topk_scores
    if spec["reduction"] == "threshold":
        out["hits"] = res.hits
        out["survivor_rows"] = res.survivor_rows
    if keep_rows:
        out["best"], out["loc"] = res.best_scores, res.best_locs
    return out


def closed_loop(stack: drive.Stack, reads: Iterator[gen.Read],
                traffic: dict, seconds: float,
                sample: Optional[drive.Reservoir], trace: bool = False,
                max_batches: Optional[int] = None) -> drive.Window:
    """Batches of reads until ``seconds`` have passed (or ``max_batches``)."""
    svc = stack.service
    spec = {k: traffic[k] for k in ("reduction", "k", "threshold")
            if k in traffic}
    keep_rows = traffic.get("check_rows", False)
    batch = traffic["batch"]
    win = drive.Window()
    clock = drive.clock
    t0 = clock()
    with drive.annotate("bench.window", trace):
        while True:
            with drive.annotate("bench.submit", trace):
                group = [next(reads) for _ in range(batch)]
                queries = [drive.make_query(r.masks, spec) for r in group]
            t_sub = clock()
            tickets, n = drive.serve(svc, queries)
            win.n_ticks += n
            t = clock()
            win.tick_s.append(t - t_sub)
            done = []
            for r, tk in zip(group, tickets):
                win.attempted += 1
                if not tk.done or tk.error is not None:
                    win.failed += 1
                    win.errors.append(repr(tk.error))
                    continue
                win.completed += 1
                done.append((tk.result, tk.cached))
                if sample is not None:
                    sample.offer(lambda r=r, tk=tk: Sample(
                        r.masks, dict(spec), answer_of(tk.result, spec,
                                                       keep_rows),
                        tk.cached))
            drive.note_results(win, done)
            if t - t0 >= seconds or (max_batches is not None and
                                     win.attempted >= max_batches * batch):
                break
    win.seconds = t - t0
    return win


# -- warm-up -----------------------------------------------------------------

def bucket(n: int) -> int:
    """The power of two a filtered launch pads ``n`` survivor rows to."""
    return max(8, 1 << (int(n) - 1).bit_length())


def launched(tickets) -> List[tuple]:
    """(plan, survivor rows or None) of each launch among ``tickets``."""
    launches = {id(t.result.plan): t.result for t in tickets
                if t.done and t.error is None and not t.cached}
    return [((r.plan.backend, r.plan.mode, r.plan.strategy,
              r.plan.chunk_rows),
             None if r.survivor_rows is None else len(r.survivor_rows))
            for r in launches.values()]


def warm_buckets(svc, reads: Iterator[gen.Read], traffic: dict,
                 lo: int, hi: int, tries: int) -> Dict[int, int]:
    """Reach every survivor bucket from ``lo`` to ``hi`` with batches of
    the mix's reads; returns the survivor count that reached each.

    The filtered verify stage compiles its programs once per power-of-two
    survivor count, and the mix's own batches reach the rarer counts only
    now and then, so a window would compile them.  Here a batch's survivor
    count is steered by its reads' thresholds, each within one of the
    mix's own ``t``: at level ``l`` the reads take ``l`` one-character
    loosenings from the pattern length ``P`` between them, spread evenly
    (read ``i`` gets ``P - l // batch`` or one less), from all reads at
    ``t + 1`` to all at ``t - 1``; survivors grow with ``l``.  Each bucket
    is searched on ``l`` with fresh reads every try: by bisection once a
    try has gone past it, else one step up at a time (one loosened read
    can add tens of thousands of survivors, and every bucket reached
    compiles).  The coalesced launch, its kernels and the survivors'
    shapes are those the window runs; the threshold is an operand of the
    verify stage, so only the filter kernel, which takes its slack as a
    constant, compiles more.  A try counts only where the planner chose
    the plan it chooses at the mix's own threshold (the first try); one
    it plans otherwise, as a scan of every row, counts as too loose.
    """
    batch = traffic["batch"]
    seen: Dict[int, int] = {}
    tried: List[tuple] = []           # (level, survivors) of every try
    own = None                        # the plan at the mix's threshold
    want = [1 << e for e in range(lo.bit_length() - 1, hi.bit_length())]
    for _ in range(tries):
        todo = [b for b in want if b not in seen]
        if not todo:
            break
        group = [next(reads) for _ in range(batch)]
        P = group[0].masks.size
        mid = (P - traffic["threshold"]) * batch
        target = todo[0]
        below = [l for l, s in tried if s <= target // 2]
        above = [l for l, s in tried if s > target]
        a = max(below, default=mid - batch - 1)
        b = min((l for l in above if l > a), default=None)
        if not tried:
            level = mid
        elif b is None or b - a <= 1:
            level = a + 1             # one read more loosened, or a retry
        else:
            level = (a + b) // 2
        level = max(mid - batch, min(mid + batch, level))
        queries = [drive.make_query(r.masks, {
            "reduction": "threshold",
            "threshold": P - level // batch - int(i < level % batch)})
            for i, r in enumerate(group)]
        tickets, _ = drive.serve(svc, queries)
        for t in tickets:
            if t.error is not None:
                raise t.error
        for plan, n in launched(tickets):
            own = plan if own is None else own
            if plan != own or n is None:
                n = float("inf")
            elif n > 0:
                seen.setdefault(bucket(n), n)
            tried.append((level, n))
    return seen


def warm_up(stack: drive.Stack, reads: Iterator[gen.Read], traffic: dict
            ) -> Dict[str, object]:
    """Warm the cell's shapes before the window: the survivor buckets the
    mix names in ``warm_survivors`` ([lo, hi, tries]), then
    ``warmup_batches`` batches of the mix as it is (which also leaves the
    filter's selectivity estimate as the mix sets it)."""
    out: Dict[str, object] = {}
    if "warm_survivors" in traffic:
        lo, hi, tries = traffic["warm_survivors"]
        out["buckets"] = warm_buckets(stack.service, reads, traffic, lo, hi,
                                      tries)
    closed_loop(stack, reads, traffic, float("inf"), None,
                max_batches=traffic["warmup_batches"])
    out["batches"] = traffic["warmup_batches"]
    return out


# -- the check ---------------------------------------------------------------

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


def numbers(win: drive.Window, samples: List[Sample], ref: List[dict]
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
