"""Calibrated cost model bench: the three proofs behind DESIGN.md Sec. 3i.

The autotuned table (``repro.match.calibrate``) replaces the static
``TPU_V5E`` constants with curves fitted to the kernels as they actually
run on this substrate.  This bench demonstrates the claim is load-bearing
rather than cosmetic, with three machine-checked proofs:

* **decisions differ** -- over the golden shape matrix the calibrated
  planner must pick a different kernel than the static one on >= 1 real
  shape (on the interpret-mode container it flips the tiny-shape ref
  escape and the large-Q mxu crossover);
* **never slower** -- on every validation-grid shape where the two
  sources disagree, the calibrated choice's *measured* wall time must
  not exceed the static choice's measured wall time (equal choices are
  trivially tied and are not re-measured);
* **feedback converges** -- an engine running with runtime recording
  against a deliberately-wrong source (static pricing in interpret mode
  is off by orders of magnitude) must re-price the hot bucket so its
  post-feedback estimate lands within the 2x drift bound of observed
  wall time.

Emits ``BENCH_match_calibrate.json`` at the repo root.  CI runs
``--smoke``: a fast-grid in-process autotune (no table I/O, so the guard
is self-contained on any runner), the cheap half of the validation grid,
and a shorter feedback loop -- same schema, artifact not rewritten.

The full validation grid deliberately omits the golden matrix's
(R=2048, Q=256) shape: static picks mxu there and measuring that pick in
interpret mode costs tens of seconds for no extra coverage (the same
mxu-vs-swar flip is already proven at R=512, Q=128).
"""

from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

import numpy as np

BENCH_JSON = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_match_calibrate.json"

# Validation grid for the never-slower proof (planner vocabulary).  The
# smoke subset keeps only shapes whose static choice is cheap to measure.
FULL_SHAPES = (
    dict(n_rows=2, fragment_chars=20, pattern_chars=8),
    dict(n_rows=64, fragment_chars=128, pattern_chars=16),
    dict(n_rows=512, fragment_chars=1024, pattern_chars=100),
    dict(n_rows=512, fragment_chars=1024, pattern_chars=100, n_patterns=128),
    dict(n_rows=4096, fragment_chars=256, pattern_chars=32, n_patterns=64),
    dict(n_rows=16384, fragment_chars=256, pattern_chars=32),
)
SMOKE_SHAPES = (
    dict(n_rows=2, fragment_chars=20, pattern_chars=8),
    dict(n_rows=64, fragment_chars=128, pattern_chars=16),
)

FULL = dict(repeats=2, shapes=FULL_SHAPES, fb=dict(R=16384, F=256, P=32),
            fb_runs=8, tol=1.25)
SMOKE = dict(repeats=1, shapes=SMOKE_SHAPES, fb=dict(R=2048, F=128, P=16),
             fb_runs=6, tol=1.5)

REQUIRED_KEYS = ("interpret", "smoke", "device_kind", "backend",
                 "calibration", "n_processes", "n_hosts", "table",
                 "decisions", "n_decisions_differ", "never_slower",
                 "feedback")
REQUIRED_NS_KEYS = ("shape", "static_choice", "calibrated_choice", "differs",
                    "static_s", "calibrated_s", "ratio", "ok")
REQUIRED_FB_KEYS = ("runs", "static_base_s", "est_s", "observed_s", "ratio",
                    "converged", "n_repriced", "store")


def _measure_choice(backend: str, shape: dict, interpret: bool,
                    repeats: int) -> float:
    """Measured wall seconds of one planner choice at one query shape.

    Mirrors how the engine actually dispatches each backend: SWAR fuses Q
    patterns as extra row tiles, the MXU batches Q natively, and the jnp
    reference makes Q sequential passes.
    """
    from repro.match import calibrate
    from repro.match.planner import kernel_name

    R, F = shape["n_rows"], shape["fragment_chars"]
    P = shape["pattern_chars"]
    Q = shape.get("n_patterns", 1)
    kernel = kernel_name(backend, shape.get("predicate", "exact"))
    if kernel in ("swar", "swar_masks"):
        rows = -(-max(R, 1) // 8) * 8 * Q
        _, t = calibrate.measure(kernel, dict(R=rows, F=F, P=P),
                                 interpret=interpret, repeats=repeats)
    elif kernel == "mxu":
        _, t = calibrate.measure(kernel, dict(R=max(R, 8), F=F, P=P, Q=Q),
                                 interpret=interpret, repeats=repeats)
    else:
        _, t = calibrate.measure("ref", dict(R=R, F=F, P=P),
                                 interpret=interpret, repeats=repeats)
        t *= Q
    return t


def never_slower_rows(calib_source, cfg: dict, interpret: bool) -> list:
    """Measure static vs. calibrated choices over the validation grid."""
    from repro.core.tech import StaticCostSource
    from repro.match.planner import Planner

    p_static = Planner(cost_source=StaticCostSource())
    p_calib = Planner(cost_source=calib_source)
    rows = []
    for shape in cfg["shapes"]:
        key = ",".join(f"{k}={v}" for k, v in sorted(shape.items()))
        a = p_static.plan(**shape).backend
        b = p_calib.plan(**shape).backend
        if a == b:
            t = _measure_choice(a, shape, interpret, cfg["repeats"])
            ta, tb, ratio, ok = t, t, 1.0, True
        else:
            ta = _measure_choice(a, shape, interpret, cfg["repeats"])
            tb = _measure_choice(b, shape, interpret, cfg["repeats"])
            ratio = tb / max(ta, 1e-12)
            ok = tb <= ta * cfg["tol"]
        rows.append({"shape": key, "static_choice": a,
                     "calibrated_choice": b, "differs": a != b,
                     "static_s": round(ta, 6), "calibrated_s": round(tb, 6),
                     "ratio": round(ratio, 4), "ok": ok})
    return rows


def feedback_convergence(cfg: dict) -> dict:
    """Run a recording engine against static pricing; check convergence.

    Static pricing in interpret mode misses by orders of magnitude, so
    the feedback loop must publish a re-priced factor for the hot
    (kernel, shape-bucket) and the engine's subsequent estimate must land
    within the 2x drift bound of the observed wall time.  The backend is
    pinned so the proof exercises one bucket instead of the explore
    flip-flop between mispriced kernels.
    """
    from repro.match import MatchEngine, MatchQuery

    fb = cfg["fb"]
    rng = np.random.default_rng(7)
    frags = rng.integers(0, 4, (fb["R"], fb["F"]), np.uint8)
    pat = np.ascontiguousarray(frags[0, :fb["P"]])
    eng = MatchEngine(frags, record_runtimes=True)
    q = MatchQuery.exact(pat, backend="swar")

    walls = []
    for _ in range(cfg["fb_runs"]):
        t0 = time.perf_counter()
        eng.match(q)
        walls.append(time.perf_counter() - t0)

    plan = eng.compile(q).plan
    r_price = (plan.n_rows if plan.backend == "ref"
               else -(-plan.n_rows // plan.n_shards))
    price = lambda **kw: eng.planner.backend_seconds(
        plan.backend, r_price, plan.n_locs, plan.pattern_chars,
        plan.n_patterns, plan.predicate, **kw)
    est, base = price(), price(base=True)
    obs = statistics.median(walls[-3:])
    ratio = max(est / obs, obs / est)
    snap = eng.planner.feedback.snapshot()
    return {
        "runs": cfg["fb_runs"],
        "shape": {k: int(v) for k, v in fb.items()},
        "static_base_s": round(base, 8),
        "est_s": round(est, 6),
        "observed_s": round(obs, 6),
        "ratio": round(ratio, 3),
        "converged": ratio <= 2.0,
        "n_repriced": snap["n_repriced"],
        "store": snap,
    }


def validate(record: dict) -> None:
    """Schema guard: fail loudly if the BENCH artifact is malformed."""
    for key in REQUIRED_KEYS:
        if key not in record:
            raise ValueError(f"BENCH record missing key {key!r}")
    if not record["calibration"].startswith("calibrated:"):
        raise ValueError("bench did not run under a calibrated source: "
                         f"{record['calibration']!r}")
    if record["n_decisions_differ"] < 1:
        raise ValueError("calibrated decisions identical to static on "
                         "every golden shape: calibration is not "
                         "load-bearing on this substrate")
    if not record["never_slower"]:
        raise ValueError("BENCH record has no never-slower rows")
    for row in record["never_slower"]:
        for key in REQUIRED_NS_KEYS:
            if key not in row:
                raise ValueError(f"never-slower row missing {key!r}: {row}")
        if not row["ok"]:
            raise ValueError(
                f"calibrated choice SLOWER than static on {row['shape']}: "
                f"{row['calibrated_choice']}={row['calibrated_s']}s vs "
                f"{row['static_choice']}={row['static_s']}s")
    fb = record["feedback"]
    for key in REQUIRED_FB_KEYS:
        if key not in fb:
            raise ValueError(f"feedback block missing key {key!r}")
    if not fb["converged"]:
        raise ValueError(
            f"feedback did not converge: est={fb['est_s']}s vs "
            f"observed={fb['observed_s']}s (ratio {fb['ratio']} > 2)")
    if fb["n_repriced"] < 1:
        raise ValueError("feedback loop never re-priced the hot bucket")
    json.loads(json.dumps(record))      # round-trips as JSON


def run_bench(smoke: bool) -> dict:
    from repro.core.tech import StaticCostSource
    from repro.kernels import default_interpret
    from repro.match import calibrate

    cfg = SMOKE if smoke else FULL
    interpret = default_interpret()
    if smoke:
        # Self-contained on any runner: fast in-process autotune, no
        # table I/O (the committed table may describe other hardware).
        table = calibrate.autotune(fast=True, interpret=interpret)
        source = table.cost_source()
    else:
        source = calibrate.load_cost_source(interpret=interpret)
        if source is None:
            table = calibrate.autotune(interpret=interpret)
            table.save()
            source = table.cost_source()

    static_dec = calibrate.golden_decisions(StaticCostSource())
    calib_dec = calibrate.golden_decisions(source)
    decisions = [{"shape": k, "static": a, "calibrated": b,
                  "differs": a != b}
                 for (k, a), (_, b) in zip(static_dec, calib_dec)]

    record = {
        "interpret": interpret,
        "smoke": smoke,
        **calibrate.bench_provenance(source),
        "table": {"tag": source.tag,
                  "curves": {k: {"alpha": c.alpha, "beta": c.beta,
                                 "rel_err": c.rel_err,
                                 "n_samples": c.n_samples}
                             for k, c in sorted(source.curves.items())}},
        "decisions": decisions,
        "n_decisions_differ": sum(d["differs"] for d in decisions),
        "never_slower": never_slower_rows(source, cfg, interpret),
        "feedback": feedback_convergence(cfg),
    }
    validate(record)
    if not smoke:
        # Smoke mode (the CI schema guard) must not clobber the committed
        # full-run artifact with the reduced grid.
        BENCH_JSON.write_text(json.dumps(record, indent=2) + "\n")
    return record


def run(smoke: bool = False):
    """``benchmarks.run`` driver hook: (name, us_per_call, derived) rows."""
    record = run_bench(smoke)
    fb = record["feedback"]
    rows = [("calibrate/decisions", 0.0,
             f"differ={record['n_decisions_differ']}/"
             f"{len(record['decisions'])} tag={record['calibration']}")]
    rows += [
        (f"calibrate/never_slower[{r['shape']}]",
         round(r["calibrated_s"] * 1e6, 1),
         f"static={r['static_choice']}:{r['static_s']*1e6:.1f}us "
         f"calib={r['calibrated_choice']} ratio={r['ratio']} ok={r['ok']}")
        for r in record["never_slower"]
    ]
    rows.append(("calibrate/feedback", round(fb["observed_s"] * 1e6, 1),
                 f"est_us={fb['est_s']*1e6:.1f} ratio={fb['ratio']} "
                 f"converged={fb['converged']} "
                 f"repriced={fb['n_repriced']}"))
    return rows


def artifact_summary() -> str:
    """One greppable line from the committed artifact (perf trajectory)."""
    if not BENCH_JSON.exists():
        return ""
    rec = json.loads(BENCH_JSON.read_text())
    fb = rec["feedback"]
    n_ok = sum(r["ok"] for r in rec["never_slower"])
    return (f"{BENCH_JSON.name} calib={rec['calibration']} "
            f"differ={rec['n_decisions_differ']}/{len(rec['decisions'])} "
            f"never_slower={n_ok}/{len(rec['never_slower'])} "
            f"fb_ratio={fb['ratio']} repriced={fb['n_repriced']}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast in-process autotune + reduced grid (CI "
                         "schema guard)")
    args = ap.parse_args()
    try:
        record = run_bench(args.smoke)
    except ValueError as e:
        print(f"BENCH validation failed: {e}", file=sys.stderr)
        return 1
    print(f"calibration: {record['calibration']} on "
          f"{record['device_kind']}/{record['backend']} "
          f"interpret={record['interpret']}")
    for d in record["decisions"]:
        mark = "DIFF" if d["differs"] else "same"
        print(f"  decision[{d['shape']}] static={d['static']} "
              f"calibrated={d['calibrated']} {mark}")
    for r in record["never_slower"]:
        print(f"  never_slower[{r['shape']}] "
              f"static={r['static_choice']}:{r['static_s']*1e3:.2f}ms "
              f"calib={r['calibrated_choice']}:{r['calibrated_s']*1e3:.2f}ms"
              f" ratio={r['ratio']} ok={r['ok']}")
    fb = record["feedback"]
    print(f"  feedback est={fb['est_s']*1e3:.2f}ms "
          f"observed={fb['observed_s']*1e3:.2f}ms ratio={fb['ratio']} "
          f"converged={fb['converged']} repriced={fb['n_repriced']} "
          f"(static base {fb['static_base_s']*1e3:.4f}ms)")
    if args.smoke:
        print("smoke: record validated, artifact not written")
    else:
        print(f"wrote {BENCH_JSON}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
