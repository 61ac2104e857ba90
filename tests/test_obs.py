"""Observability layer tests (DESIGN.md Sec. 3l).

Covers the contracts the layer is trusted for:

* ``LogHistogram`` quantiles within one bucket width of exact numpy
  percentiles, over several distributions;
* span nesting / attribute / stage-breakdown invariants, including the
  disjoint self-time accounting;
* Chrome/Perfetto trace-event export schema;
* plan-vs-actual records agreeing **bit-for-bit** with what
  ``FeedbackStore.observe`` receives on a feedback-enabled engine;
* the disabled fast path allocating nothing (singleton no-op span,
  tracemalloc-asserted);
* the AST lint (``tools/lint_obs_spans.py``) passing on the tree and
  catching a planted uncovered dispatch;
* ``MatchResult.timings`` / ``ServiceStats`` histogram views.
"""

from __future__ import annotations

import gc
import json
import math
import pathlib
import subprocess
import sys
import tracemalloc

import numpy as np
import pytest

from repro.obs import (NOOP_SPAN, STAGES, LogHistogram, MetricsRegistry,
                       Observability, Tracer)

REPO = pathlib.Path(__file__).resolve().parent.parent
LINT = REPO / "tools" / "lint_obs_spans.py"


# -- LogHistogram ------------------------------------------------------------

@pytest.mark.parametrize("dist", ["lognormal", "uniform", "exponential",
                                  "bimodal"])
def test_histogram_quantiles_within_one_bucket(dist):
    rng = np.random.default_rng(3)
    if dist == "lognormal":
        xs = rng.lognormal(-5, 2, 5000)
    elif dist == "uniform":
        xs = rng.uniform(1e-4, 1e-1, 5000)
    elif dist == "exponential":
        xs = rng.exponential(0.01, 5000)
    else:
        xs = np.concatenate([rng.normal(1e-3, 1e-4, 2500),
                             rng.normal(1e-1, 1e-2, 2500)])
        xs = np.abs(xs) + 1e-9
    h = LogHistogram()
    for x in xs:
        h.record(float(x))
    for q in (0.01, 0.25, 0.50, 0.90, 0.95, 0.99):
        true = float(np.quantile(xs, q, method="lower"))
        est = h.quantile(q)
        assert est > 0.0
        # One bucket width of log-error max (plus the min/max clamp can
        # only *reduce* the error).
        assert abs(math.log(est) - math.log(true)) <= math.log(h.base) \
            + 1e-9, (dist, q, est, true)


def test_histogram_edge_cases():
    h = LogHistogram()
    assert h.quantile(0.5) == 0.0 and h.mean == 0.0
    h.record(0.0)                      # underflow bucket
    h.record(-1.0)
    h.record(4.0)
    assert h.count == 3 and h.n_under == 2
    assert h.quantile(0.0) == 0.0      # underflow sorts first
    assert h.quantile(1.0) == pytest.approx(4.0)   # clamped to max
    assert h.sum == pytest.approx(3.0)
    snap = h.snapshot()
    assert snap["count"] == 3 and "p99" in snap
    with pytest.raises(ValueError):
        h.quantile(1.5)
    with pytest.raises(ValueError):
        LogHistogram(base=1.0)


def test_histogram_single_value_exact():
    h = LogHistogram()
    for _ in range(100):
        h.record(0.125)
    for q in (0.0, 0.5, 1.0):
        assert h.quantile(q) == pytest.approx(0.125)


# -- spans -------------------------------------------------------------------

def test_span_nesting_and_attrs():
    tr = Tracer(enabled=True)
    with tr.span("service.tick", {"tick": 0}) as t:
        with tr.span("match.run") as r:
            with tr.span("plan", {"kernel": "swar"}) as p:
                p.set("est_seconds", np.float64(0.5))
            with tr.span("launch", {"c0": 0}):
                pass
        assert tr.current() is t
    assert tr.current() is None
    assert len(tr.roots) == 1
    root = tr.roots[0]
    assert [s.name for s in root.walk()] == \
        ["service.tick", "match.run", "plan", "launch"]
    assert r.parent_id == root.span_id
    assert p.attrs["kernel"] == "swar"
    # numpy scalar coerced to a plain JSON float
    assert isinstance(p.attrs["est_seconds"], float)
    assert root.duration_s >= r.duration_s >= 0.0
    # span ids unique + parent ids resolve within the tree
    ids = [s.span_id for s in root.walk()]
    assert len(set(ids)) == len(ids)
    for s in root.walk():
        if s.parent_id is not None:
            assert s.parent_id in ids


def test_stage_seconds_disjoint():
    tr = Tracer(enabled=True)
    with tr.span("match.run") as root:
        with tr.span("filter"):
            with tr.span("pull"):     # nested stage: counts as pull only
                pass
        with tr.span("launch"):
            pass
    stages = root.stage_seconds()
    assert set(stages) == set(STAGES)
    fil = next(s for s in root.children if s.name == "filter")
    pull = fil.children[0]
    # Disjoint self-times: filter excludes the nested pull.
    assert stages["pull"] == pytest.approx(pull.duration_s)
    assert stages["filter"] == pytest.approx(
        fil.duration_s - pull.duration_s)
    assert sum(stages.values()) <= root.duration_s + 1e-9


def test_span_exception_unwind():
    tr = Tracer(enabled=True)
    with pytest.raises(RuntimeError):
        with tr.span("a"):
            with tr.span("b"):
                raise RuntimeError("boom")
    assert tr.current() is None        # stack fully unwound
    assert [s.name for s in tr.iter_spans()] == ["a", "b"]


def test_max_spans_bounds_roots():
    tr = Tracer(enabled=True, max_spans=2)
    for _ in range(5):
        with tr.span("r"):
            pass
    assert len(tr.roots) == 2 and tr.n_dropped == 3
    assert tr.n_spans == 5


# -- export ------------------------------------------------------------------

def test_chrome_trace_schema(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("match.run", {"reduction": "best"}):
        with tr.span("launch"):
            pass
    path = tmp_path / "trace.json"
    n = tr.write_chrome(path)
    trace = json.loads(path.read_text())
    assert n == 2 and len(trace["traceEvents"]) == 2
    for ev in trace["traceEvents"]:
        assert set(("name", "cat", "ph", "ts", "dur", "pid", "tid",
                    "args")) <= set(ev)
        assert ev["ph"] == "X"
        assert ev["ts"] >= 0.0 and ev["dur"] >= 0.0
    # child starts within parent's [ts, ts+dur] (Perfetto nests by
    # time containment)
    parent, child = trace["traceEvents"]
    assert parent["ts"] <= child["ts"]
    assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] \
        + 1.0   # 1us slack for float rounding
    assert trace["otherData"]["n_spans"] == 2


def test_jsonl_export(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("a", {"x": 1}):
        with tr.span("b"):
            pass
    path = tmp_path / "spans.jsonl"
    assert tr.write_jsonl(path) == 2
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    assert [r["name"] for r in recs] == ["a", "b"]
    assert recs[1]["parent_id"] == recs[0]["span_id"]
    assert recs[0]["attrs"] == {"x": 1}


# -- disabled fast path ------------------------------------------------------

def test_disabled_span_is_singleton_noop():
    tr = Tracer(enabled=False)
    s = tr.span("anything", None)
    assert s is NOOP_SPAN
    assert tr.span("other") is s       # same object every call
    with s as inner:
        inner.set("k", "v")            # swallowed
    assert tr.n_spans == 0 and tr.roots == []


def test_disabled_span_zero_allocations():
    tr = Tracer(enabled=False)

    def hot():
        for _ in range(100):
            with tr.span("launch"):
                pass

    hot()                              # warm any lazy state
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    hot()
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    # Zero allocations attributable to the obs layer itself (the test
    # harness's own snapshot bookkeeping is excluded by the filter).
    grew = [st for st in after.compare_to(before, "lineno")
            if st.size_diff > 0
            and any("repro" in str(f) and "obs" in str(f)
                    for f in st.traceback)]
    assert not grew, f"disabled span path allocated: {grew[:3]}"


# -- registry / plan-vs-actual ----------------------------------------------

def test_registry_instruments():
    m = MetricsRegistry()
    m.counter("x").inc()
    m.counter("x").inc(2)
    m.gauge("g").set(1.5)
    m.histogram("h").record(0.25)
    assert m.counter("x").value == 3
    assert m.gauge("g").value == 1.5
    snap = m.snapshot()
    assert snap["counters"]["x"] == 3
    assert snap["histograms"]["h"]["count"] == 1
    json.dumps(snap)                   # JSON-safe end to end


def test_plan_actual_mispredict_accounting():
    m = MetricsRegistry(drift_bound=2.0)
    key = ("swar", 5, 3, 0)
    m.record_plan_actual(key, 1.0, 1.5)     # within bound
    m.record_plan_actual(key, 1.0, 8.0)     # outside
    m.record_plan_actual(key, 0.0, 1.0)     # degenerate -> mispredict
    assert m.mispredict_rate() == pytest.approx(2 / 3)
    assert m.mispredict_rate("swar") == pytest.approx(2 / 3)
    assert m.mispredict_rate("mxu") == 0.0
    summary = m.plan_actual_summary()
    assert summary["swar/5/3/0"]["n"] == 3
    assert summary["swar/5/3/0"]["last_obs_s"] == 1.0


def test_plan_actual_matches_feedback_bit_for_bit():
    """Every (key, est, obs) the engine hands FeedbackStore.observe is
    the identical record in the obs registry (same tuples, same float
    bits) -- the two accountings are one accounting."""
    from repro.match import MatchEngine

    rng = np.random.default_rng(5)
    rows = rng.integers(0, 4, (48, 64), np.uint8)
    eng = MatchEngine(rows, record_runtimes=True)
    observed = []
    orig = eng.planner.feedback.observe
    eng.planner.feedback.observe = (
        lambda key, est, obs: (observed.append((key, est, obs)),
                               orig(key, est, obs))[-1])
    for i in range(4):
        eng.match(rows[i, :12].copy())
    eng.match(rows[0, :12].copy(), reduction="threshold", threshold=12.0)
    assert observed, "feedback-enabled engine recorded nothing"
    records = eng.obs.metrics.plan_actual_records
    assert len(records) >= len(observed)
    # every feedback observation appears verbatim (tuple identity +
    # float equality, not approx) in the registry's record list
    reg = {(k, e, o) for k, e, o in records}
    for key, est, obs in observed:
        assert (key, est, obs) in reg
    # and the registry saw them under the same kernel names
    kernels = {k[0] for k, _, _ in records}
    assert kernels <= {"swar", "mxu", "ref", "filter"}


def test_plan_actual_always_on_without_feedback():
    from repro.match import MatchEngine

    rng = np.random.default_rng(6)
    rows = rng.integers(0, 4, (32, 64), np.uint8)
    eng = MatchEngine(rows, record_runtimes=False)
    eng.match(rows[0, :8].copy())
    eng.match(rows[1, :8].copy())
    assert eng.planner.feedback.n_observations == 0
    assert eng.obs.metrics.plan_actual      # registry recorded anyway
    assert eng.obs.metrics.mispredict_rate() >= 0.0


# -- engine / service integration -------------------------------------------

@pytest.fixture(scope="module")
def traced_service():
    from repro.match import MatchEngine, MatchService

    rng = np.random.default_rng(9)
    rows = rng.integers(0, 4, (48, 64), np.uint8)
    obs = Observability(spans=True)
    eng = MatchEngine(rows, obs=obs)
    svc = MatchService(eng)
    pats = [rows[i, :10].copy() for i in range(6)]
    tickets = [svc.submit(p) for p in pats]
    svc.ingest(rng.integers(0, 4, (4, 64), np.uint8))
    svc.flush()
    return svc, tickets, obs


def test_match_result_timings(traced_service):
    svc, tickets, obs = traced_service
    res = tickets[0].result
    assert res.timings is not None
    assert set(res.timings) == set(STAGES)
    assert all(v >= 0.0 for v in res.timings.values())
    assert res.timings["launch"] > 0.0
    # timings excluded from the dataclass repr (compact result)
    assert "timings" not in repr(res)


def test_timings_absent_when_disabled():
    from repro.match import MatchEngine

    rng = np.random.default_rng(10)
    rows = rng.integers(0, 4, (32, 64), np.uint8)
    eng = MatchEngine(rows)            # obs default: spans off
    res = eng.match(rows[0, :8].copy())
    assert res.timings is None
    assert eng.obs.tracer.n_spans == 0


def test_service_stats_histogram_views(traced_service):
    svc, tickets, obs = traced_service
    s = svc.stats
    assert s.latency_hist.count == s.n_completed
    # deprecated running-sum accessors remain as thin views
    assert s.total_latency_s == pytest.approx(s.latency_hist.sum)
    assert s.avg_latency_s == pytest.approx(
        s.latency_hist.sum / s.n_completed)
    snap = s.snapshot()
    assert 0.0 < snap["latency_p50_s"] <= snap["latency_p95_s"] \
        <= snap["latency_p99_s"]
    # snapshot rounds to 6 decimals, which can nudge p99 above the true
    # max by up to 5e-7 -- tolerance must cover the rounding step
    assert snap["latency_p99_s"] <= s.latency_hist.max + 1e-6
    assert set(snap["timings"]) == set(STAGES)
    assert snap["plan_actual"]
    assert snap["plan_mispredict_rate"] >= 0.0
    json.dumps(snap)


def test_service_trace_covers_stages(traced_service):
    svc, tickets, obs = traced_service
    spans = list(obs.tracer.iter_spans())
    names = {s.name for s in spans}
    assert {"service.enqueue", "service.tick", "match.run", "plan",
            "launch", "merge", "pull", "pack"} <= names
    n_enq = sum(s.name == "service.enqueue" for s in spans)
    assert n_enq == svc.stats.n_submitted
    for run in (s for s in spans if s.name == "match.run"):
        sub = {c.name for c in run.walk()}
        assert {"plan", "launch", "pull"} <= sub


def test_corpus_counters(traced_service):
    svc, tickets, obs = traced_service
    counters = obs.metrics.counters
    assert counters["corpus.packs"].value >= 1
    assert counters["corpus.splice_rows"].value >= 4   # the ingest


# -- lint --------------------------------------------------------------------

def test_lint_passes_on_tree():
    proc = subprocess.run([sys.executable, str(LINT)],
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    assert "OK" in proc.stdout


def test_lint_catches_uncovered_dispatch(tmp_path):
    k = tmp_path / "src" / "repro" / "kernels"
    m = tmp_path / "src" / "repro" / "match"
    k.mkdir(parents=True)
    m.mkdir(parents=True)
    (k / "foo.py").write_text(
        "import jax.experimental.pallas as pl\n"
        "def kern(x):\n"
        "    return pl.pallas_call(lambda r: r, name='kern')(x)\n")
    (m / "eng.py").write_text(
        "from repro.kernels import foo as _f\n"
        "def run(x):\n"
        "    return _f.kern(x)\n")
    bad = subprocess.run([sys.executable, str(LINT), str(tmp_path)],
                         capture_output=True, text=True)
    assert bad.returncode == 1
    assert "eng.py:3" in bad.stderr
    (m / "eng.py").write_text(
        "from repro.kernels import foo as _f\n"
        "def run(x, tr):\n"
        "    with tr.span('launch'):\n"
        "        return _f.kern(x)\n")
    good = subprocess.run([sys.executable, str(LINT), str(tmp_path)],
                          capture_output=True, text=True)
    assert good.returncode == 0, good.stderr


def test_lint_catches_unnamed_kernel(tmp_path):
    """A kernel's name is what the device trace shows and the roofline
    readers match: a pallas_call must pass it as a literal."""
    k = tmp_path / "src" / "repro" / "kernels"
    m = tmp_path / "src" / "repro" / "match"
    k.mkdir(parents=True)
    m.mkdir(parents=True)
    (m / "eng.py").write_text(
        "from repro.kernels import foo as _f\n"
        "def run(x, tr):\n"
        "    with tr.span('launch'):\n"
        "        return _f.kern(x)\n")
    for call, ok in [("pl.pallas_call(lambda r: r)", False),
                     ("pl.pallas_call(lambda r: r, name=NAME)", False),
                     ("pl.pallas_call(lambda r: r, name='kern')", True),
                     ("pl.pallas_call(lambda r: r, name='a' if x else 'b')",
                      True)]:
        (k / "foo.py").write_text(
            "import jax.experimental.pallas as pl\n"
            "NAME = 'kern'\n"
            "def kern(x):\n"
            f"    return {call}(x)\n")
        proc = subprocess.run([sys.executable, str(LINT), str(tmp_path)],
                              capture_output=True, text=True)
        assert (proc.returncode == 0) == ok, (call, proc.stderr)
        if not ok:
            assert ("foo.py:4: pallas_call without a literal name="
                    in proc.stderr)


# -- host.gc -----------------------------------------------------------------

def _well_formed(tr):
    """Every span closed, filed under the span it names as parent, with
    unique ids; nothing left open."""
    assert tr._stack == []
    seen = set()

    def visit(sp, parent):
        assert sp.t1 is not None and sp.t1 >= sp.t0
        assert sp.parent_id == (parent.span_id if parent else None)
        assert sp.span_id not in seen
        seen.add(sp.span_id)
        for ch in sp.children:
            visit(ch, sp)
    for r in tr.roots:
        visit(r, None)
    return seen


def test_full_gc_inside_nested_spans_is_one_host_gc_child():
    tr = Tracer(enabled=True)
    with tr.span("a"):
        with tr.span("b") as b:
            gc.collect()
            assert tr._stack[-1] is b
    assert [c.name for c in b.children] == ["host.gc"]
    assert b.children[0].duration_s > 0.0
    assert len(_well_formed(tr)) == tr.n_spans == 3
    gc.collect(0)                      # a young collection gets no span
    assert sum(s.name == "host.gc" for s in tr.iter_spans()) == 1


class _CollectingAnnotation:
    """A profiler annotation that runs a full collection as it opens and
    closes: a collection inside ``Span.__enter__`` and ``__exit__``."""

    entered = 0

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        if self.name != "host.gc":
            gc.collect()
        _CollectingAnnotation.entered += 1

    def __exit__(self, *exc):
        if self.name != "host.gc":
            gc.collect()


def test_gc_inside_span_enter_and_exit_keeps_the_tree():
    tr = Tracer(enabled=True)
    tr._annotation = _CollectingAnnotation
    _CollectingAnnotation.entered = 0
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    names = [s.name for s in tr.iter_spans()]
    assert names.count("host.gc") == 4 and names[:2] == ["outer", "host.gc"]
    assert len(_well_formed(tr)) == tr.n_spans == len(names)
    # Each collection's own annotation opened around it, beside the two
    # spans' annotations.
    assert _CollectingAnnotation.entered == 6


def test_gc_at_any_allocation_keeps_the_tree():
    tr = Tracer(enabled=True)
    was = gc.get_threshold()
    gc.set_threshold(1, 1, 1)
    try:
        for i in range(300):
            with tr.span("a", {"i": i}):
                with tr.span("b") as b:
                    b.set("x", [i])
    finally:
        gc.set_threshold(*was)
    assert len(_well_formed(tr)) == tr.n_spans
    assert sum(s.name == "a" for s in tr.iter_spans()) == 300


def test_gc_hook_only_while_enabled():
    gc.collect()
    n = len(gc.callbacks)
    off = Tracer(enabled=False)
    Observability()
    assert len(gc.callbacks) == n and off._gc_hook is None
    off.enabled = True
    hook = off._gc_hook
    assert hook in gc.callbacks
    off.enabled = False
    assert hook not in gc.callbacks and off._gc_hook is None
    gc.collect()
    assert off.n_spans == 0
    on = Tracer(enabled=True)
    hook = on._gc_hook
    assert hook in gc.callbacks
    del on
    gc.collect()                       # a dropped tracer unhooks itself
    assert hook not in gc.callbacks


# -- pull, timings and the service snapshot ----------------------------------

def test_pull_has_wait_and_copy_children(traced_service):
    svc, tickets, obs = traced_service
    pulls = [s for s in obs.tracer.iter_spans() if s.name == "pull"]
    assert pulls
    for p in pulls:
        assert [c.name for c in p.children] == ["pull.wait", "pull.copy"]


def _without(span, names):
    """A copy of ``span``'s tree with spans named in ``names`` spliced
    out (their children lifted to the parent)."""
    from repro.obs import Span
    out = Span(span.tracer, span.name, None)
    out.t0, out.t1 = span.t0, span.t1

    def lift(ch):
        if ch.name in names:
            return [g for c in ch.children for g in lift(c)]
        return [_without(ch, names)]
    out.children = [g for c in span.children for g in lift(c)]
    return out


def test_timings_ignore_the_non_stage_spans(traced_service):
    svc, tickets, obs = traced_service
    extra = {"chunk.host", "result", "filter.launch", "filter.union",
             "pull.wait", "pull.copy", "host.gc"}
    runs = [s for s in obs.tracer.iter_spans() if s.name == "match.run"]
    assert any(c.name in extra for r in runs for c in r.walk())
    for r in runs:
        assert r.stage_seconds() == _without(r, extra).stage_seconds()


SNAPSHOT_FIELDS = {
    "n_submitted", "n_completed", "n_cache_hits", "n_launches",
    "n_coalesced_launches", "n_coalesced_queries", "n_sequential_fallback",
    "n_failed", "n_ingested_rows", "n_ingest_batches", "n_ticks",
    "launches_last_tick", "avg_launches_per_tick", "cache_hit_rate",
    "n_filtered_launches", "filter_hit_rate", "avg_survivor_frac",
    "avg_latency_s", "latency_p50_s", "latency_p95_s", "latency_p99_s",
    "qps", "n_shards", "shard_rows", "shard_balance", "merge_path",
    "collective_bytes", "cost_source", "misprediction_rate", "feedback",
    "n_bank_launches", "n_bank_prefilter_launches", "n_bank_hits",
    "n_evicted_rows", "n_compactions", "bank", "timings",
    "plan_actual", "plan_mispredict_rate"}


def test_snapshot_reads_at_read_time_what_ticks_mirrored():
    """The views a tick used to copy into the stats (shards, cost source,
    feedback, bank and window counters, stage timings, plan-vs-actual)
    read the same values from their owners when the snapshot is taken."""
    from repro.match import (MatchEngine, MatchQuery, MatchService,
                             PackedCorpus, PatternBank)
    rng = np.random.default_rng(33)
    F, P = 64, 12
    frags = rng.integers(0, 4, (24, F), np.uint8)
    eng = MatchEngine(PackedCorpus(frags, capacity=256),
                      obs=Observability(spans=True))
    bank = PatternBank(F, P, capacity=8)
    svc = MatchService(eng, bank=bank, window_rows=30,
                       compact_dead_frac=0.3)
    bank.register(frags[3, 5:5 + P].copy(), threshold=P)
    for i in range(5):
        svc.ingest(rng.integers(0, 4, (8, F), np.uint8))
        for j in range(3):
            svc.submit(MatchQuery.exact(frags[(i + j) % 24, :P].copy()))
        svc.tick()
    snap = svc.stats.snapshot()
    assert set(snap) == SNAPSHOT_FIELDS
    json.dumps(snap)
    m = eng.obs.metrics
    assert snap["n_shards"] == 1
    assert snap["shard_rows"] == [int(x) for x in eng.shard_live_rows()]
    assert snap["cost_source"] == eng.planner.cost_source.tag
    assert snap["feedback"] == eng.planner.feedback.snapshot()
    assert snap["n_bank_launches"] == bank.n_bank_launches == 5
    assert snap["n_bank_prefilter_launches"] == bank.n_prefilter_launches
    assert snap["bank"] == bank.stats()
    assert snap["n_compactions"] == eng.corpus.n_compactions > 0
    assert snap["plan_actual"] == m.plan_actual_summary()
    assert snap["plan_mispredict_rate"] == round(m.mispredict_rate(), 4)
    assert set(snap["timings"]) == set(STAGES)
    assert snap["timings"] == svc._tick_timings
    # A tick sets no gauge; whoever reads the registry publishes them.
    assert "service.queue_depth" not in m.gauges
    svc.submit(MatchQuery.exact(frags[0, :P].copy()))
    svc.publish_gauges()
    assert m.gauge("service.queue_depth").value == 1
    assert m.gauge("service.n_compactions").value == snap["n_compactions"]
