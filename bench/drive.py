"""Drive the match stack through ``MatchService`` as a cell's clients do.

A closed loop: one mapper submits a batch of reads, ticks the service
until every ticket is done, and submits the next.  The window closes at
the first batch completion after ``--seconds``; its length runs from the
window's start to that completion.  The loop keeps a seeded reservoir
sample of the window's answers for the reference check, and a record of
what each batch launched.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, Iterator, List, Optional

import numpy as np

from bench import gen

clock = time.perf_counter


@dataclasses.dataclass
class Stack:
    engine: object
    service: object


def row_mesh(devices):
    """A one-axis mesh over the cell's chips: the engine shards corpus
    rows over its ``data`` axis (the ``rows`` rule's default).  None for
    one chip, which runs unsharded."""
    if len(devices) < 2:
        return None
    import jax
    from jax.sharding import AxisType
    return jax.make_mesh((len(devices),), ("data",),
                         axis_types=(AxisType.Auto,), devices=devices)


def build_stack(config: dict, rows: np.ndarray, devices,
                trace: bool) -> Stack:
    """The deployment on the cell's chips: a resident corpus with its
    q-gram index, an engine and a service, as the configuration states
    them; rows are sharded over the chips where there are several."""
    from repro.match import (CorpusIndex, MatchEngine, MatchService,
                             Observability, PackedCorpus)

    obs = Observability(spans=trace, profiler=trace)
    corpus = PackedCorpus(rows)
    index = CorpusIndex(corpus, q=config["filter"]["q"],
                        n_bits=config["filter"]["bits"])
    engine = MatchEngine(corpus, index=index, obs=obs,
                         mesh=row_mesh(devices))
    service = MatchService(engine, cache_size=config["service"]["cache_size"])
    return Stack(engine, service)


def make_query(masks: np.ndarray, spec: dict):
    from repro.match import MatchQuery
    kw = {"reduction": spec["reduction"]}
    if spec["reduction"] == "topk":
        kw["k"] = spec["k"]
    if spec["reduction"] == "threshold":
        kw["threshold"] = spec["threshold"]
    return MatchQuery.from_masks(masks, **kw)


BENCH_SPANS = {"bench.window", "bench.submit"}


@contextlib.contextmanager
def annotate(name: str, on: bool):
    """A span of the benchmark's own on the profiler's clock."""
    if not on:
        yield
        return
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


# -- what a window records ---------------------------------------------------

RESULT_FIELDS = ("best_locs", "best_scores", "topk_rows", "topk_scores",
                 "hits", "scores", "survivor_rows")


@dataclasses.dataclass
class Sample:
    """One answer kept for the reference check."""

    masks: np.ndarray
    spec: dict                       # reduction, k or threshold
    answer: Dict[str, np.ndarray]
    cached: bool = False


class Reservoir:
    """A seeded uniform sample of fixed size from a stream (Algorithm R)."""

    def __init__(self, size: int, r: np.random.Generator):
        self.size, self.r, self.seen = int(size), r, 0
        self.items: List[Sample] = []

    def offer(self, make) -> None:
        """Offer the next item; ``make()`` builds it only when it is kept."""
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append(make())
            return
        j = int(self.r.integers(0, self.seen))
        if j < self.size:
            self.items[j] = make()


@dataclasses.dataclass
class Window:
    seconds: float = 0.0             # start to close
    attempted: int = 0               # reads submitted in the window
    completed: int = 0
    failed: int = 0                  # errored or never done
    launches: List[dict] = dataclasses.field(default_factory=list)
    result_bytes: int = 0
    answered: int = 0                # queries whose result bytes counted
    n_ticks: int = 0
    tick_s: List[float] = dataclasses.field(default_factory=list)
    errors: List[str] = dataclasses.field(default_factory=list)


def note_results(win: Window, results: list) -> None:
    """Launch records and result bytes of one tick's completed queries.

    Scattered views of one fused launch share its ``plan`` object, so
    distinct plans among the non-cached results are the tick's launches.
    """
    seen = set()
    for res, cached in results:
        win.answered += 1
        win.result_bytes += sum(getattr(res, f).nbytes for f in RESULT_FIELDS
                                if getattr(res, f, None) is not None)
        if cached or id(res.plan) in seen:
            continue
        seen.add(id(res.plan))
        p = res.plan
        surv = res.survivor_rows
        win.launches.append({
            "backend": p.backend, "predicate": p.predicate, "mode": p.mode,
            "strategy": p.strategy, "n_rows": int(p.n_rows),
            "rows_scanned": int(p.n_rows if surv is None else len(surv)),
            "n_patterns": int(p.n_patterns), "pattern_chars":
            int(p.pattern_chars), "n_locs": int(p.n_locs),
            "chunk_rows": int(p.chunk_rows), "cost_source": p.cost_source,
            "survivor_frac": res.survivor_frac})


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


# -- closed loop -------------------------------------------------------------

def serve(svc, queries: list) -> tuple:
    """Submit one batch and tick the service until every ticket is done
    (or a tick per query has passed); returns (tickets, ticks)."""
    tickets = [svc.submit(q) for q in queries]
    n = 0
    while n <= len(queries) and not all(t.done for t in tickets):
        svc.tick()
        n += 1
    return tickets, n


def closed_loop(stack: Stack, reads: Iterator[gen.Read], traffic: dict,
                seconds: float, sample: Optional[Reservoir],
                trace: bool = False, max_batches: Optional[int] = None
                ) -> Window:
    """Batches of reads until ``seconds`` have passed (or ``max_batches``)."""
    svc = stack.service
    spec = {k: traffic[k] for k in ("reduction", "k", "threshold")
            if k in traffic}
    keep_rows = traffic.get("check_rows", False)
    batch = traffic["batch"]
    win = Window()
    t0 = clock()
    with annotate("bench.window", trace):
        while True:
            with annotate("bench.submit", trace):
                group = [next(reads) for _ in range(batch)]
                queries = [make_query(r.masks, spec) for r in group]
            t_sub = clock()
            tickets, n = serve(svc, queries)
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
            note_results(win, done)
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
        queries = [make_query(r.masks, {
            "reduction": "threshold",
            "threshold": P - level // batch - int(i < level % batch)})
            for i, r in enumerate(group)]
        tickets, _ = serve(svc, queries)
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


def warm_up(stack: Stack, reads: Iterator[gen.Read], traffic: dict
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
