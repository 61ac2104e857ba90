"""What every kind's client shares: the deployment's stack, the window's
record, a seeded sample of its answers, and one batch served through
``MatchService``.

A kind's client (``bench/kinds/<kind>.py``) drives the stack through the
service as the cell's clients do, keeps a ``Reservoir`` of the window's
answers for the reference check, and records in a ``Window`` what each
tick launched.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import List

import numpy as np

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


class Reservoir:
    """A seeded uniform sample of fixed size from a stream (Algorithm R)."""

    def __init__(self, size: int, r: np.random.Generator):
        self.size, self.r, self.seen = int(size), r, 0
        self.items: list = []

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
    attempted: int = 0               # requests submitted in the window
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


# -- one batch ---------------------------------------------------------------

def serve(svc, queries: list) -> tuple:
    """Submit one batch and tick the service until every ticket is done
    (or a tick per query has passed); returns (tickets, ticks)."""
    tickets = [svc.submit(q) for q in queries]
    n = 0
    while n <= len(queries) and not all(t.done for t in tickets):
        svc.tick()
        n += 1
    return tickets, n
