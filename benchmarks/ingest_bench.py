"""Online-ingestion bench: corpus growth under live query traffic.

The regime the paper's resident-reference design exists for (DESIGN.md
Sec. 3f): the store keeps serving queries while new documents stream in.
Two scenarios:

* ``service_mixed`` -- a ``MatchService`` over one resident corpus takes
  interleaved ``ingest`` + ``submit`` traffic; each tick applies one
  batched in-place ``append_rows`` then serves the tick's queries.
  Reported: docs/s ingested *while* serving, and QPS served *while*
  ingesting.  Asserted: zero host repacks of resident rows across all
  growth (pack counters flat after the warm-up pack), and the final
  post-growth results bit-identical to a fresh engine packed from scratch
  on the grown corpus.
* ``dedup_growth`` -- a ``CRAMDedup`` store crosses its capacity boundary
  under ``filter`` traffic.  Asserted: the store's ``MatchEngine`` is the
  same object before and after growth (no rebuild on doubling) and the
  lifetime pack counters stay <= one per device form.

Both paths run on the planner's choice of kernel; correctness is asserted
before any number is reported.  Emits ``BENCH_match_ingest.json`` at the
repo root and exits nonzero if the record is malformed.  CI runs
``--smoke`` as a schema guard: same pipeline and validation on a reduced
shape, without overwriting the committed full-run artifact.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

BENCH_JSON = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_match_ingest.json"

FULL = dict(R0=64, F=256, P=32, n_docs=192, ingest_batch=4, q_per_tick=2,
            dedup_docs=80)
SMOKE = dict(R0=16, F=128, P=16, n_docs=24, ingest_batch=4, q_per_tick=1,
             dedup_docs=12)

REQUIRED_KEYS = ("shape", "device_kind", "backend", "calibration",
                 "n_processes", "n_hosts", "interpret", "smoke", "results")
REQUIRED_RESULT_KEYS = ("scenario", "n_docs", "docs_per_s",
                        "resident_repacks", "engine_stable", "identical")


def bench_service_mixed(cfg: dict, rng) -> dict:
    """Mixed ingest+query ticks through one MatchService."""
    from repro.match import MatchEngine, MatchQuery, MatchService

    R0, F, P = cfg["R0"], cfg["F"], cfg["P"]
    frags = rng.integers(0, 4, (R0, F), np.uint8)
    eng = MatchEngine(frags)
    svc = MatchService(eng)
    docs = rng.integers(0, 4, (cfg["n_docs"], F), np.uint8)
    pats = rng.integers(0, 4, (cfg["n_docs"], P), np.uint8)

    # Warm-up: build the device forms and the jit caches so the timed
    # loop (and the pack-counter assertion) isolates growth.
    svc.match(MatchQuery.exact(pats[0]))
    rows_before = eng.corpus.n_rows

    n_q = 0
    t0 = time.perf_counter()
    for i in range(0, cfg["n_docs"], cfg["ingest_batch"]):
        svc.ingest(docs[i:i + cfg["ingest_batch"]])
        for j in range(cfg["q_per_tick"]):
            svc.submit(MatchQuery.exact(pats[(i + j) % len(pats)]))
            n_q += 1
        svc.tick()
    svc.flush()
    dt = time.perf_counter() - t0

    n_docs = eng.corpus.n_rows - rows_before
    # Packs beyond the lazy first one per form are resident repacks; a
    # first-pack of the *other* form (batched roofline flipping kernels)
    # is legitimate and must not trip the invariant.
    repacks = (max(0, eng.corpus.swar_pack_count - 1)
               + max(0, eng.corpus.onehot_pack_count - 1))
    # Post-growth correctness: the served store must be bit-identical to
    # an engine packed from scratch on the grown corpus.
    probe = MatchQuery.exact(pats[1])
    got = svc.match(probe)
    oracle = MatchEngine(np.array(eng.corpus.fragments)).match(probe)
    identical = (np.array_equal(got.best_scores, oracle.best_scores)
                 and np.array_equal(got.best_locs, oracle.best_locs))
    return {
        "scenario": "service_mixed",
        "n_docs": int(n_docs),
        "docs_per_s": round(n_docs / dt, 1),
        "qps_while_ingesting": round(n_q / dt, 1),
        "n_queries_served": n_q,
        "rows": [int(rows_before), int(eng.corpus.n_rows)],
        "capacity": int(eng.corpus.capacity),
        "resident_repacks": int(repacks),
        "engine_stable": True,           # the service never rebuilds it
        "identical": bool(identical),
        "ingest_batches": svc.stats.n_ingest_batches,
        "service_stats": svc.stats.snapshot(),
    }


def bench_dedup_growth(cfg: dict, rng) -> dict:
    """CRAMDedup crossing its capacity boundary: no engine rebuild."""
    from repro.data.dedup import CRAMDedup, _INITIAL_CAPACITY

    d = CRAMDedup(threshold=1.01)        # never a duplicate: every doc adds
    engine_before = d.engine
    n = max(cfg["dedup_docs"], _INITIAL_CAPACITY + 8)  # force >= 1 doubling
    docs = [rng.bytes(cfg["F"]) for _ in range(n)]
    t0 = time.perf_counter()
    kept = d.filter(docs)
    dt = time.perf_counter() - t0
    engine_stable = d.engine is engine_before
    return {
        "scenario": "dedup_growth",
        "n_docs": len(kept),
        "docs_per_s": round(len(kept) / dt, 1),
        "rows": [0, len(d)],
        "capacity": d.capacity,
        # Lazy first pack per form is the warm-up, not a repack of
        # resident rows; growth must add zero on top of one per form.
        "resident_repacks": (
            max(0, d.engine.corpus.swar_pack_count - 1)
            + max(0, d.engine.corpus.onehot_pack_count - 1)),
        "host_packs": d.total_host_packs,
        "row_writes": d.total_row_writes,
        "engine_stable": bool(engine_stable),
        "identical": len(kept) == n,     # threshold>1: nothing may drop
    }


def validate(record: dict) -> None:
    """Schema guard: fail loudly if the BENCH artifact is malformed."""
    for key in REQUIRED_KEYS:
        if key not in record:
            raise ValueError(f"BENCH record missing key {key!r}")
    if not (record["calibration"] == "static"
            or record["calibration"].startswith("calibrated:")):
        raise ValueError("malformed calibration provenance: "
                         f"{record['calibration']!r}")
    if not record["results"]:
        raise ValueError("BENCH record has no results")
    for row in record["results"]:
        for key in REQUIRED_RESULT_KEYS:
            if key not in row:
                raise ValueError(f"result row missing key {key!r}: {row}")
        if row["resident_repacks"] != 0:
            raise ValueError(
                f"{row['scenario']}: {row['resident_repacks']} host "
                "repack(s) of resident rows during growth (must be 0)")
        if not row["engine_stable"]:
            raise ValueError(f"{row['scenario']}: engine was rebuilt on "
                             "growth")
        if not row["identical"]:
            raise ValueError(f"{row['scenario']}: post-growth results "
                             "diverged from the from-scratch oracle")
        if row["docs_per_s"] <= 0:
            raise ValueError(f"{row['scenario']}: non-positive ingest "
                             "throughput")
    json.loads(json.dumps(record))      # round-trips as JSON


def run_bench(smoke: bool) -> dict:
    from repro.kernels import default_interpret

    cfg = SMOKE if smoke else FULL
    rng = np.random.default_rng(11)
    results = [bench_service_mixed(cfg, rng), bench_dedup_growth(cfg, rng)]
    from repro.match.calibrate import bench_provenance
    record = {
        "shape": {k: cfg[k] for k in
                  ("R0", "F", "P", "n_docs", "ingest_batch", "q_per_tick")},
        **bench_provenance(),
        "interpret": default_interpret(),
        "smoke": smoke,
        "results": results,
    }
    validate(record)
    if not smoke:
        # Smoke mode (the CI schema guard) must not clobber the committed
        # full-run artifact with reduced shapes.
        BENCH_JSON.write_text(json.dumps(record, indent=2) + "\n")
    return record


def run(smoke: bool = False):
    """``benchmarks.run`` driver hook: (name, us_per_call, derived) rows."""
    record = run_bench(smoke)
    return [
        (f"ingest/{row['scenario']}",
         round(1e6 / max(row["docs_per_s"], 1e-9), 1),
         f"docs_per_s={row['docs_per_s']} "
         f"repacks={row['resident_repacks']} "
         f"engine_stable={row['engine_stable']} "
         f"identical={row['identical']}")
        for row in record["results"]
    ]


def artifact_summary() -> str:
    """One greppable line from the committed artifact (perf trajectory)."""
    if not BENCH_JSON.exists():
        return ""
    rec = json.loads(BENCH_JSON.read_text())
    cases = " ".join(f"{r['scenario']}:docs_per_s={r['docs_per_s']}:"
                     f"repacks={r['resident_repacks']}"
                     for r in rec["results"])
    return f"{BENCH_JSON.name} {cases}"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small corpus + fewer docs (CI schema guard)")
    args = ap.parse_args()
    try:
        record = run_bench(args.smoke)
    except ValueError as e:
        print(f"BENCH validation failed: {e}", file=sys.stderr)
        return 1
    for row in record["results"]:
        extra = (f"  qps={row['qps_while_ingesting']}"
                 if "qps_while_ingesting" in row else "")
        print(f"{row['scenario']:>14}  docs/s={row['docs_per_s']:>8.1f}"
              f"{extra}  repacks={row['resident_repacks']}  "
              f"engine_stable={row['engine_stable']}  "
              f"identical={row['identical']}")
    if args.smoke:
        print("smoke: record validated, artifact not written")
    else:
        print(f"wrote {BENCH_JSON}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
