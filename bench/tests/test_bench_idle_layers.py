"""The five ``idle_*_share`` readers: they split the device's idle share
by the program layer that left it idle.

On synthetic reductions and on the trace recorded on a v5e they add up,
with the client's remainder, to ``device_idle_share``; a traced CPU run
of the service shows that every span the program emits is counted by
exactly one of them.
"""

from __future__ import annotations

import gc
import importlib.util

import jax
import numpy as np
import pytest

from bench import spec, tracing
from bench.tests import test_bench_trace as recorded_trace
from bench.tests import tiny
from bench.tracing import Reduced

LAYERS = ("idle_service_share", "idle_dispatch_share",
          "idle_engine_host_share", "idle_pull_share", "idle_gc_share")

# What the five leave to the client: the benchmark's own spans and
# stretches no span covers.
CLIENT = ("bench.window", "bench.submit", "bench.idle", "none")

# Every name the program's spans carry, as the readers' tables place them.
PROGRAM = {
    "idle_service_share": {"service.enqueue", "service.tick",
                           "service.cache", "service.plan",
                           "service.coalesce", "service.complete"},
    "idle_dispatch_share": {"launch", "merge", "filter.launch"},
    "idle_engine_host_share": {"match.run", "plan", "pack", "filter",
                               "filter.union", "chunk.host", "result",
                               "compact", "bank.scan"},
    "idle_pull_share": {"pull", "pull.wait", "pull.copy"},
    "idle_gc_share": {"host.gc"},
}


def module(stem):
    path = spec.BENCH_DIR / "metrics" / f"{stem}.py"
    s = importlib.util.spec_from_file_location(f"idle_{stem}", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def readers():
    return {stem: module(stem) for stem in LAYERS}


def owners(readers, name):
    return [stem for stem, mod in readers.items() if mod.counts(name)]


def reduced(gaps, window_s=1.0):
    idle = sum(s for _, s in gaps)
    return Reduced(window_s=window_s, busy_s=window_s - idle,
                   busy_by_device={"/device:TPU:0": window_s - idle},
                   op_seconds={}, gaps=list(gaps), n_devices=1)


def readings(red):
    ctx = {"trace": red}
    out = {stem: spec.metric_reader(f"{stem}.readmap")(ctx)
           for stem in LAYERS}
    out["device_idle_share"] = spec.metric_reader(
        "device_idle_share.readmap")(ctx)
    return out


def client(red):
    return 100.0 * sum(s for n, s in red.gaps if n.startswith("bench.")
                       or n == "none") / red.window_s


def test_tables_are_disjoint_and_leave_the_client_alone(readers):
    for stem, names in PROGRAM.items():
        for name in names:
            assert owners(readers, name) == [stem], name
    for name in CLIENT:
        assert owners(readers, name) == [], name


@pytest.mark.parametrize("window_s", [1.0, 40.936])
def test_readers_and_client_sum_to_the_idle_share(window_s):
    r = np.random.default_rng(3)
    names = sorted(set().union(*PROGRAM.values())) + list(CLIENT)
    gaps = [(names[i % len(names)], float(r.uniform(0, window_s / 400)))
            for i in range(300)]
    red = reduced(gaps, window_s)
    got = readings(red)
    parts = sum(got[stem] for stem in LAYERS) + client(red)
    assert parts == pytest.approx(got["device_idle_share"], rel=1e-12)
    for stem in LAYERS:
        own = sum(s for n, s in gaps if n in PROGRAM[stem])
        assert got[stem] == pytest.approx(100.0 * own / window_s,
                                          rel=1e-12)


@pytest.mark.parametrize("stem", LAYERS)
def test_no_gap_of_its_own_reads_zero_and_no_trace_none(stem):
    read = spec.metric_reader(f"{stem}.readmap")
    others = [(n, 0.01) for s, names in PROGRAM.items() if s != stem
              for n in sorted(names)] + [("none", 0.02)]
    assert read({"trace": reduced(others)}) == 0.0
    assert read({"trace": reduced([])}) == 0.0
    assert read({"trace": None}) is None


def test_recorded_trace():
    path = str(spec.ROOT / recorded_trace.RECORDED)
    red = tracing.reduce(tracing.load(path),
                         span_names=recorded_trace.SPANS)
    got = readings(red)
    w = red.window_s
    # The gaps ``test_recorded_trace_gaps_by_span`` expects.
    totals = dict(red.gap_totals())
    assert got["idle_pull_share"] == pytest.approx(
        100.0 * 0.005162203 / w, abs=1e-6)
    assert got["idle_service_share"] == pytest.approx(
        100.0 * sum(s for n, s in totals.items()
                    if n.startswith("service.")) / w, rel=1e-12)
    assert got["idle_gc_share"] == 0.0
    parts = sum(got[stem] for stem in LAYERS) + client(red)
    assert parts == pytest.approx(got["device_idle_share"], abs=1e-9)
    assert client(red) >= 100.0 * 0.009614232 / w - 1e-9   # bench.idle


def tiny_stack(seed=2 ** 31 + 5):
    """The tiny tree's read-mapping deployment, traced, and its reads."""
    from bench import drive, gen
    from repro.match import MatchQuery
    real = spec.load_cell(tiny.CELLS[0])
    config = dict(real["config"], **tiny.sizes("configs",
                                               real["cell"]["config"]))
    F, stride = config["fragment_chars"], config["row_stride"]
    P, G = config["pattern_chars"], config["genome_bp"]
    g = gen.genome(seed, G, F, stride)
    rows = gen.fold(g, G, F, stride)
    stack = drive.build_stack(config, rows, jax.devices()[:1], trace=True)
    starts = gen.rng(seed, gen.READS).integers(0, G - P, 8)
    masks = [gen.exact_masks(g[s:s + P]) for s in starts]
    topk = [MatchQuery.from_masks(m, reduction="topk", k=2)
            for m in masks[:4]]
    v2 = [MatchQuery.from_masks(m, reduction="threshold", threshold=98,
                                filter=True) for m in masks[4:]]
    return stack, topk, v2


def test_every_span_the_program_emits_has_one_reader(readers):
    stack, topk, v2 = tiny_stack()
    svc = stack.service
    for batch in (topk, v2):
        tickets = [svc.submit(q) for q in batch]
        svc.flush()
        assert all(t.done and t.error is None for t in tickets)
    assert all(t.result.plan.strategy == "filter" for t in tickets)
    gc.collect()
    names = {s.name for s in stack.engine.obs.tracer.iter_spans()}
    assert {"pull.wait", "pull.copy", "chunk.host", "result",
            "filter.launch", "filter.union", "service.cache",
            "service.plan", "service.complete", "host.gc"} <= names
    for name in names:
        assert len(owners(readers, name)) == 1, name
