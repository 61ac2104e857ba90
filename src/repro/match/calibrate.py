"""Autotuned cost-model calibration (DESIGN.md Sec. 3i).

The paper's evaluation never trusts datasheet numbers: every system-level
claim flows from device-level parameter extraction (Sec. 4).  The
planner's static ``TPU_V5E`` constants are exactly such untrusted
numbers on any substrate but the one they describe -- this container
runs the kernels in Pallas interpret mode on CPU, where the static
model's absolute times are off by orders of magnitude and its relative
*decisions* (mxu vs. swar, tiny-shape ref escapes) are simply wrong.

``autotune()`` closes the loop: microbenchmark the actual kernels
(``match_swar``, ``match_swar_masks``, ``match_mxu``, ``filter_qgram``,
the jnp reference) at a small grid of shapes on the current backend, and
fit, per kernel, the two-parameter overhead curve

    measured = alpha * analytic + beta

where *analytic* is the planner's roofline estimate for the same shape
(``planner.analytic_*_seconds``).  ``alpha`` is the measured overhead
factor over the op/byte model (the SNIPPETS.md Sec. 2 idiom); ``beta``
is the measured per-dispatch intercept.  Fitting a curve over the
analytic model -- not a raw shape-indexed lookup table -- means unseen
shapes interpolate through the same arithmetic, and the calibrated
pricing inherits the analytic model's monotonicity in R, P, Q (the
positivity clamps below make that a hard guarantee).

Fitted parameters are **quantized to quarter-octave log2 bins** (~+-9%)
before use: two back-to-back calibration runs on a quiet machine land in
the same bins, so timing noise cannot flip near-tie plan decisions
nondeterministically (the CI stability gate asserts this).

Tables persist as JSON keyed by (device kind, backend, interpret flag)
under ``<repo>/calibration/`` (override with ``REPRO_CALIBRATION_DIR``);
``load_cost_source()`` returns the matching ``CalibratedCostSource`` or
``None``, so callers degrade to the static fallback when no table fits
the current substrate.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import math
import os
import re
import time
from pathlib import Path
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.tech import (TPU_V5E, CalibratedCostSource, CostSource,
                             KernelCurve, TPURoofline)
from repro.kernels import default_interpret
from repro.kernels import filter_qgram as _fq
from repro.kernels import match_mxu as _mxu
from repro.kernels import match_swar as _swar
from repro.kernels import ref as _kref
from repro.match.planner import (Planner, _mxu_geometry, _swar_geometry,
                                 analytic_filter_seconds,
                                 analytic_mxu_seconds, analytic_ref_seconds,
                                 analytic_swar_seconds)

TABLE_VERSION = 1
KERNELS = ("swar", "swar_masks", "mxu", "ref", "filter")

# Measurement grid: a handful of shapes per kernel spanning ~2 decades of
# analytic cost, enough to pin a 2-parameter curve.  Shapes are dicts of
# the planner's own vocabulary (R rows, F fragment chars, P pattern
# chars, Q patterns; sig_words for the filter kernel).  Row counts
# respect the kernel tiles (swar: 8, filter: 128).
FULL_GRID: Dict[str, List[dict]] = {
    "swar": [
        dict(R=256, F=128, P=16),
        dict(R=1024, F=128, P=16),
        dict(R=4096, F=128, P=16),
        dict(R=1024, F=256, P=32),
        dict(R=2048, F=512, P=64),
    ],
    "swar_masks": [
        dict(R=256, F=128, P=16),
        dict(R=1024, F=128, P=16),
        dict(R=1024, F=256, P=32),
        dict(R=2048, F=512, P=64),
    ],
    "mxu": [
        dict(R=64, F=128, P=16, Q=128),
        dict(R=256, F=128, P=16, Q=128),
        dict(R=256, F=256, P=32, Q=128),
        dict(R=512, F=256, P=64, Q=128),
    ],
    "ref": [
        dict(R=64, F=128, P=16),
        dict(R=512, F=128, P=16),
        dict(R=1024, F=256, P=32),
    ],
    "filter": [
        dict(R=1024, sig_words=8),
        dict(R=4096, sig_words=8),
        dict(R=16384, sig_words=8),
    ],
}

# Reduced grid for CI: 2 shapes per kernel, cheapest ones, still enough
# for the 2-parameter fit (and the stability gate only needs the same
# *decisions*, not tight curves).
FAST_GRID: Dict[str, List[dict]] = {
    # The third swar/mxu shapes sit in the batched-Q crossover regime the
    # golden matrix probes, so the fast fit interpolates that decision
    # instead of extrapolating into it (extrapolated fast fits flipped
    # near-crossover decisions run to run).
    "swar": [dict(R=256, F=128, P=16), dict(R=2048, F=128, P=16),
             dict(R=512, F=1024, P=100)],
    "swar_masks": [dict(R=256, F=128, P=16), dict(R=2048, F=128, P=16)],
    "mxu": [dict(R=64, F=128, P=16, Q=128), dict(R=256, F=128, P=16, Q=128),
            dict(R=256, F=256, P=32, Q=128)],
    # ref's fixed per-call cost dominates small shapes; the fast pair
    # must reach a slope-resolvable shape or the 2-point fit degenerates.
    "ref": [dict(R=64, F=128, P=16), dict(R=1024, F=256, P=32)],
    "filter": [dict(R=1024, sig_words=8), dict(R=8192, sig_words=8)],
}

# Golden shape matrix for decision-stability and persistence round-trip
# checks: the planner inputs whose *decisions* (kernel choice) must be
# identical across a table save/load and across two back-to-back
# calibration runs.  Spans the regimes where the static and calibrated
# models disagree on this container: tiny shapes (static's TINY_OPS ->
# ref escape), large batched Q (static's mxu crossover), accept-set
# predicates, and plain scans.
GOLDEN_SHAPES: Tuple[dict, ...] = (
    dict(n_rows=2, fragment_chars=20, pattern_chars=8),
    dict(n_rows=64, fragment_chars=128, pattern_chars=16),
    dict(n_rows=512, fragment_chars=1024, pattern_chars=100),
    dict(n_rows=512, fragment_chars=1024, pattern_chars=100, n_patterns=128),
    dict(n_rows=4096, fragment_chars=256, pattern_chars=32, n_patterns=64),
    dict(n_rows=16384, fragment_chars=256, pattern_chars=32),
    dict(n_rows=1024, fragment_chars=256, pattern_chars=48,
         predicate="accept"),
    dict(n_rows=2048, fragment_chars=512, pattern_chars=64, n_patterns=256),
)

# A plan flip between two calibration runs is tolerated only when it is
# cost-neutral: the two choices price within this factor of each other
# under either table.  Quarter-octave quantization makes genuine flips
# of near-ties rare, but two curves can each land one bin apart between
# runs (2^0.25 each, ~1.41 combined); the bound sits just under that so
# it tolerates quantization-edge flips while still failing real ones.
STABILITY_COST_TOL = 1.35


# -- substrate identity -------------------------------------------------------

def device_kind() -> str:
    """Kind string of the default device (e.g. "cpu", "TPU v5e")."""
    return jax.devices()[0].device_kind


def backend_name() -> str:
    return jax.default_backend()


def _slug(s: str) -> str:
    return re.sub(r"[^a-z0-9]+", "-", s.lower()).strip("-") or "unknown"


def table_filename(dev_kind: str, backend: str, interpret: bool) -> str:
    mode = "interp" if interpret else "compiled"
    return f"{_slug(dev_kind)}--{_slug(backend)}--{mode}.json"


def calibration_dir() -> Path:
    """Table directory: ``REPRO_CALIBRATION_DIR`` or ``<repo>/calibration``."""
    env = os.environ.get("REPRO_CALIBRATION_DIR")
    if env:
        return Path(env)
    return Path(__file__).resolve().parents[3] / "calibration"


# -- measurement --------------------------------------------------------------

def _time_best(fn, repeats: int) -> float:
    """Min-of-N wall time of ``fn`` (first call discarded: jit compile)."""
    jax.block_until_ready(fn())
    best = math.inf
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _build_call(kernel: str, shape: Mapping, interpret: bool,
                roofline: TPURoofline):
    """(callable, analytic_s) for one (kernel, shape) measurement point."""
    rng = np.random.default_rng(0xC0FFEE)
    R = int(shape["R"])
    if kernel == "filter":
        wb = int(shape["sig_words"])
        rows = jax.numpy.asarray(
            rng.integers(0, 2**32, (wb, R), dtype=np.uint32))
        qsig, slacks = (jax.numpy.asarray(a) for a in _fq.pattern_operands(
            rng.integers(0, 2**32, (1, wb), dtype=np.uint32), [4]))
        analytic = analytic_filter_seconds(roofline, R, wb, 1)
        return (lambda: _fq.filter_qgram(rows, qsig, slacks,
                                         interpret=interpret)), analytic

    F, P = int(shape["F"]), int(shape["P"])
    L = F - P + 1
    if kernel == "ref":
        frags = jax.numpy.asarray(
            rng.integers(0, 4, (R, F), dtype=np.uint8))
        pat = jax.numpy.asarray(rng.integers(0, 4, (P,), dtype=np.uint8))
        analytic = analytic_ref_seconds(roofline, R, L, P, 1)
        return (lambda: _kref.match_scores_ref(frags, pat)), analytic

    if kernel == "mxu":
        Q = int(shape.get("Q", 128))
        l_pad, p_chars, q_pad, f_chars = _mxu_geometry(P, L, Q)
        ref_flat = jax.numpy.asarray(
            rng.integers(0, 2, (R, f_chars * 4)).astype(np.float32),
            jax.numpy.bfloat16)
        pat_mat = jax.numpy.asarray(
            rng.integers(0, 2, (p_chars * 4, q_pad)).astype(np.float32),
            jax.numpy.bfloat16)
        analytic = analytic_mxu_seconds(roofline, R, L, P, Q)
        return (lambda: _mxu.match_mxu(ref_flat, pat_mat, l_pad=l_pad,
                                       interpret=interpret)), analytic

    # swar / swar_masks
    wp, need = _swar_geometry(P, L)
    words = jax.numpy.asarray(
        rng.integers(0, 2**32, (R, need), dtype=np.uint32))
    mask_codes = np.zeros(wp * 16, np.uint32)
    mask_codes[:P] = 1
    from repro.core import encoding
    valid = jax.numpy.asarray(encoding.pack_codes_u32(mask_codes[None, :]))
    if kernel == "swar_masks":
        planes = jax.numpy.asarray(
            rng.integers(0, 2**32, (R, 4 * wp), dtype=np.uint32))
        analytic = analytic_swar_seconds(roofline, R, L, P, 1, "accept")
        return (lambda: _swar.match_swar_masks(
            words, planes, valid, n_locs=L, pattern_chars=P,
            interpret=interpret)), analytic
    pats = jax.numpy.asarray(
        rng.integers(0, 2**32, (R, wp), dtype=np.uint32))
    analytic = analytic_swar_seconds(roofline, R, L, P, 1, "exact")
    return (lambda: _swar.match_swar(
        words, pats, valid, n_locs=L, pattern_chars=P,
        interpret=interpret)), analytic


def measure(kernel: str, shape: Mapping, *, interpret: bool,
            repeats: int = 3,
            roofline: TPURoofline = TPU_V5E) -> Tuple[float, float]:
    """(analytic_s, measured_s) for one kernel at one shape."""
    fn, analytic = _build_call(kernel, shape, interpret, roofline)
    return analytic, _time_best(fn, repeats)


# -- fitting ------------------------------------------------------------------

def quantize_q2(v: float) -> float:
    """Snap ``v`` to the nearest quarter-octave log2 bin (~+-9%).

    Two calibration runs whose raw fits differ by timing noise land in
    the same bin, so the decisions they imply are bit-identical; 0 stays
    0 (a zero intercept is a legitimate fit outcome).
    """
    if v <= 0.0:
        return 0.0
    return float(2.0 ** (round(math.log2(v) * 4.0) / 4.0))


def fit_curve(analytic: Sequence[float],
              measured: Sequence[float]) -> KernelCurve:
    """Fit measured = alpha*analytic + beta, alpha > 0, beta >= 0.

    Weighted least squares with 1/y^2 weights (minimizes *relative*
    error: a 100us shape matters as much as a 100ms one -- exactly the
    property plan comparisons need).  Three constrained candidate models
    are fitted and the lowest-residual one wins:

    * the unconstrained 2-parameter fit, admitted only when it already
      satisfies alpha > 0, beta >= 0;
    * through-origin (beta = 0): right when the data is slope-dominated
      and noise pushed the free intercept negative;
    * constant-dominated (beta = weighted mean, alpha = median residual
      slope): right when the grid's slope signal drowns in the fixed
      per-call cost (the jnp reference path), where a through-origin fit
      would massively underprice small shapes -- and, worse, flip
      decisions between back-to-back runs on fit noise.

    Picking by residual is deterministic in the samples, and both
    parameters are quarter-octave quantized (see ``quantize_q2``), so
    quiet-machine reruns land on identical curves.  The positivity
    constraints make the curve monotone in the analytic estimate --
    hence in R, P, Q.
    """
    x = np.asarray(analytic, np.float64)
    y = np.asarray(measured, np.float64)
    if x.size == 0:
        raise ValueError("cannot fit a curve to zero samples")
    w = 1.0 / np.maximum(y, 1e-12) ** 2
    sxx, sx, s1 = (w * x * x).sum(), (w * x).sum(), w.sum()
    sxy, sy = (w * x * y).sum(), (w * y).sum()
    det = sxx * s1 - sx * sx

    def rel_err_of(a: float, b: float) -> float:
        pred = a * x + b
        return float(np.max(np.abs(pred - y) / np.maximum(y, 1e-12)))

    candidates = []
    if x.size >= 2 and det > 0:
        a2 = (sxy * s1 - sx * sy) / det
        b2 = (sxx * sy - sx * sxy) / det
        if a2 > 0.0 and b2 >= 0.0:
            candidates.append((a2, b2))
    a1 = sxy / max(sxx, 1e-300)           # x, y > 0, so a1 > 0 always
    candidates.append((a1, 0.0))
    bc = sy / s1
    resid = np.maximum(y - bc, 0.0) / np.maximum(x, 1e-300)
    ac = float(np.median(resid))
    if ac <= 0.0:
        # Flat data: keep a vanishing slope so pricing still grows
        # (slowly) past the grid instead of treating all shapes as free.
        ac = bc / (100.0 * float(x.max()))
    candidates.append((ac, bc))
    alpha, beta = min(candidates, key=lambda ab: rel_err_of(*ab))
    alpha, beta = quantize_q2(alpha), quantize_q2(beta)
    return KernelCurve(alpha=alpha, beta=beta, n_samples=int(x.size),
                       rel_err=round(rel_err_of(alpha, beta), 4))


# -- the table ----------------------------------------------------------------

@dataclasses.dataclass
class CalibrationTable:
    """Fitted per-kernel cost curves for one (device, backend, mode)."""

    device_kind: str
    backend: str
    interpret: bool
    curves: Dict[str, KernelCurve]
    samples: Dict[str, List[dict]] = dataclasses.field(default_factory=dict)
    meta: Dict = dataclasses.field(default_factory=dict)

    def _canonical(self) -> str:
        body = {
            "version": TABLE_VERSION,
            "device_kind": self.device_kind,
            "backend": self.backend,
            "interpret": self.interpret,
            "curves": {k: dataclasses.asdict(c)
                       for k, c in sorted(self.curves.items())},
        }
        return json.dumps(body, sort_keys=True, separators=(",", ":"))

    @property
    def digest(self) -> str:
        """Content digest of the decision-relevant fields (stable key)."""
        return hashlib.blake2b(self._canonical().encode(),
                               digest_size=16).hexdigest()

    def cost_source(self) -> CalibratedCostSource:
        return CalibratedCostSource(
            self.curves, digest=self.digest,
            meta={"device_kind": self.device_kind, "backend": self.backend,
                  "interpret": self.interpret})

    # -- persistence ----------------------------------------------------------
    def to_json(self) -> dict:
        return {
            "version": TABLE_VERSION,
            "device_kind": self.device_kind,
            "backend": self.backend,
            "interpret": self.interpret,
            "digest": self.digest,
            "curves": {k: dataclasses.asdict(c)
                       for k, c in sorted(self.curves.items())},
            "samples": self.samples,
            "meta": self.meta,
        }

    @classmethod
    def from_json(cls, doc: Mapping) -> "CalibrationTable":
        if doc.get("version") != TABLE_VERSION:
            raise ValueError(f"calibration table version "
                             f"{doc.get('version')!r} != {TABLE_VERSION}")
        curves = {k: KernelCurve(**c) for k, c in doc["curves"].items()}
        table = cls(device_kind=doc["device_kind"], backend=doc["backend"],
                    interpret=bool(doc["interpret"]), curves=curves,
                    samples=dict(doc.get("samples", {})),
                    meta=dict(doc.get("meta", {})))
        stored = doc.get("digest")
        if stored and stored != table.digest:
            raise ValueError("calibration table digest mismatch: file "
                             "edited or truncated; re-run autotune")
        return table

    def path(self, directory: Optional[Path] = None) -> Path:
        d = Path(directory) if directory is not None else calibration_dir()
        return d / table_filename(self.device_kind, self.backend,
                                  self.interpret)

    def save(self, directory: Optional[Path] = None) -> Path:
        p = self.path(directory)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(self.to_json(), indent=2, sort_keys=True)
                     + "\n")
        return p

    @classmethod
    def load(cls, dev_kind: Optional[str] = None,
             backend: Optional[str] = None,
             interpret: Optional[bool] = None,
             directory: Optional[Path] = None) -> "CalibrationTable":
        dev_kind = dev_kind if dev_kind is not None else device_kind()
        backend = backend if backend is not None else backend_name()
        interpret = (interpret if interpret is not None
                     else default_interpret())
        d = Path(directory) if directory is not None else calibration_dir()
        p = d / table_filename(dev_kind, backend, interpret)
        return cls.from_json(json.loads(p.read_text()))


def load_cost_source(dev_kind: Optional[str] = None,
                     backend: Optional[str] = None,
                     interpret: Optional[bool] = None,
                     directory: Optional[Path] = None
                     ) -> Optional[CalibratedCostSource]:
    """The persisted source for the current substrate, or None (fallback).

    This is the "calibrate once, then serve" entry point: construct the
    engine with ``cost_source=load_cost_source() or None`` -- a missing,
    unreadable, or wrong-substrate table degrades to the static fallback
    instead of failing.
    """
    try:
        return CalibrationTable.load(dev_kind, backend, interpret,
                                     directory).cost_source()
    except (OSError, ValueError, KeyError, json.JSONDecodeError):
        return None


def bench_provenance(cost_source: Optional[CostSource] = None) -> dict:
    """Provenance block every BENCH_*.json artifact carries.

    ``calibration`` is the cost-source tag that priced the run's planner
    decisions ("static" when no source was loaded), so an artifact can
    finally say what hardware -- and what cost model -- its numbers mean.
    ``n_processes`` / ``n_hosts`` record the controller topology
    (DESIGN.md Sec. 3k): a multi-controller artifact measured collective
    merges, a single-controller one did not -- numbers from the two are
    not comparable without this field.
    """
    return {
        "device_kind": device_kind(),
        "backend": backend_name(),
        "calibration": cost_source.tag if cost_source is not None
        else "static",
        "n_processes": jax.process_count(),
        "n_hosts": len({d.host_id if hasattr(d, "host_id")
                        else d.process_index for d in jax.devices()}),
    }


# -- autotune -----------------------------------------------------------------

def autotune(*, fast: bool = False, interpret: Optional[bool] = None,
             repeats: Optional[int] = None,
             roofline: TPURoofline = TPU_V5E,
             kernels: Sequence[str] = KERNELS,
             verbose: bool = False) -> CalibrationTable:
    """Measure the grid, fit per-kernel curves, return the table."""
    interpret = default_interpret() if interpret is None else interpret
    repeats = 3 if repeats is None else repeats
    grid = FAST_GRID if fast else FULL_GRID
    curves: Dict[str, KernelCurve] = {}
    samples: Dict[str, List[dict]] = {}
    for kernel in kernels:
        xs, ys, rows = [], [], []
        for shape in grid[kernel]:
            analytic, measured = measure(kernel, shape,
                                         interpret=interpret,
                                         repeats=repeats,
                                         roofline=roofline)
            xs.append(analytic)
            ys.append(measured)
            rows.append({**shape, "analytic_s": analytic,
                         "measured_s": round(measured, 6)})
            if verbose:
                print(f"  {kernel} {shape}: analytic {analytic:.3g}s "
                      f"measured {measured:.3g}s "
                      f"(x{measured / max(analytic, 1e-300):.3g})")
        curves[kernel] = fit_curve(xs, ys)
        samples[kernel] = rows
    return CalibrationTable(
        device_kind=device_kind(), backend=backend_name(),
        interpret=interpret, curves=curves, samples=samples,
        meta={"grid": "fast" if fast else "full", "repeats": repeats})


# -- decision stability -------------------------------------------------------

def golden_decisions(source: CostSource) -> List[Tuple[str, str]]:
    """(shape-key, chosen backend) over the golden matrix for one source."""
    planner = Planner(cost_source=source)
    out = []
    for shape in GOLDEN_SHAPES:
        key = ",".join(f"{k}={v}" for k, v in sorted(shape.items()))
        out.append((key, planner.plan(**shape).backend))
    return out


def decisions_stable(src_a: CostSource, src_b: CostSource,
                     tol: float = STABILITY_COST_TOL
                     ) -> Tuple[bool, List[dict]]:
    """Compare plan decisions of two sources over the golden matrix.

    A differing choice is tolerated only when it is cost-neutral: each
    source prices the other's pick within ``tol`` of its own.  Returns
    (all_stable, per-shape report rows).
    """
    pa, pb = Planner(cost_source=src_a), Planner(cost_source=src_b)
    rows, ok = [], True
    for shape in GOLDEN_SHAPES:
        plan_a, plan_b = pa.plan(**shape), pb.plan(**shape)
        stable = plan_a.backend == plan_b.backend
        neutral = False
        if not stable:
            # Price both choices under source A: a flip is harmless if A
            # thinks B's pick costs within tol of its own (and vice
            # versa).
            R = shape["n_rows"]
            P = shape["pattern_chars"]
            L = shape["fragment_chars"] - P + 1
            Q = shape.get("n_patterns", 1)
            pred = shape.get("predicate", "exact")
            a_own = pa.backend_seconds(plan_a.backend, R, L, P, Q, pred)
            a_other = pa.backend_seconds(plan_b.backend, R, L, P, Q, pred)
            b_own = pb.backend_seconds(plan_b.backend, R, L, P, Q, pred)
            b_other = pb.backend_seconds(plan_a.backend, R, L, P, Q, pred)
            neutral = (a_other <= tol * a_own and b_other <= tol * b_own)
        rows.append({"shape": ",".join(f"{k}={v}" for k, v
                                       in sorted(shape.items())),
                     "choice_a": plan_a.backend, "choice_b": plan_b.backend,
                     "stable": stable, "cost_neutral": neutral})
        ok = ok and (stable or neutral)
    return ok, rows


# -- CLI ----------------------------------------------------------------------

def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Microbenchmark the match kernels and fit the "
                    "calibrated cost table for this substrate.")
    ap.add_argument("--fast", action="store_true",
                    help="reduced grid + fewer repeats (CI mode)")
    ap.add_argument("--out", type=Path, default=None,
                    help="directory to write the table (default: "
                         "REPRO_CALIBRATION_DIR or <repo>/calibration)")
    ap.add_argument("--no-save", action="store_true",
                    help="fit and report only")
    ap.add_argument("--check-stability", action="store_true",
                    help="run the autotune twice and require identical "
                         "(or cost-neutral) golden-matrix decisions")
    args = ap.parse_args(argv)
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    table = autotune(fast=args.fast, verbose=True)
    for kernel in sorted(table.curves):
        c = table.curves[kernel]
        print(f"CALIB kernel={kernel} alpha={c.alpha:.6g} "
              f"beta={c.beta:.6g} rel_err={c.rel_err:.3g} "
              f"n={c.n_samples}")
    print(f"CALIB table device_kind={table.device_kind!r} "
          f"backend={table.backend} interpret={table.interpret} "
          f"digest={table.digest[:8]}")
    if not args.no_save:
        path = table.save(args.out)
        print(f"CALIB saved {path}")

    if args.check_stability:
        table2 = autotune(fast=args.fast)
        ok, rows = decisions_stable(table.cost_source(),
                                    table2.cost_source())
        for r in rows:
            print(f"CALIB stability shape[{r['shape']}] "
                  f"a={r['choice_a']} b={r['choice_b']} "
                  f"stable={r['stable']} neutral={r['cost_neutral']}")
        if not ok:
            print("CALIB stability FAILED: decisions flipped between "
                  "back-to-back calibration runs")
            return 1
        print("CALIB stability OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
