"""Plain reference matcher: the semantics the match stack must reproduce.

It imports nothing of the program.  A score is the number of pattern
positions whose accept mask holds the corpus base at that alignment (for
exact patterns, P minus the Hamming distance).  Per row and pattern the
best alignment is the highest score, the first such alignment on ties.
Top-k takes the k rows of highest best score, ties to the lower row id.
A threshold query returns every (row, alignment, score) with
score >= threshold.

``scores_loops`` is the brute-force definition (tests).  ``scan`` counts
every (row, alignment, pattern) score over a whole corpus on the default
JAX device, in blocks of rows, as a one-hot correlation: operands are 0
or 1 and a score is at most P, so every product and sum is exact in any
floating type the device multiplies in.  ``precision="fp8"`` rounds each
score through float8 (e4m3) before it is reduced: that is the control,
which breaks the exact-score guarantee the way a compressed result path
would.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional

import numpy as np

BLOCK_ROWS = 8192
PRECISIONS = ("exact", "fp8")


def scores_loops(rows: np.ndarray, masks: np.ndarray) -> np.ndarray:
    """(R, L, Q) scores by plain loops; ``masks`` is (Q, P) accept masks."""
    rows = np.asarray(rows)
    masks = np.atleast_2d(masks)
    R, F = rows.shape
    Q, P = masks.shape
    out = np.zeros((R, F - P + 1, Q), np.int32)
    for r in range(R):
        for loc in range(F - P + 1):
            for q in range(Q):
                out[r, loc, q] = sum(
                    int(masks[q, i]) >> int(rows[r, loc + i]) & 1
                    for i in range(P))
    return out


def scores_np(rows: np.ndarray, masks: np.ndarray) -> np.ndarray:
    """(R, L, Q) scores, vectorised NumPy (for a few rows at a time)."""
    rows = np.asarray(rows, np.uint8)
    masks = np.atleast_2d(np.asarray(masks, np.uint8))
    P = masks.shape[1]
    win = np.lib.stride_tricks.sliding_window_view(rows, P, axis=1)
    acc = (masks[None, None, :, :] >> win[:, :, None, :]) & 1
    return acc.sum(-1, dtype=np.int32)


def round_fp8(scores: np.ndarray) -> np.ndarray:
    """Scores rounded through float8 e4m3 (the control's precision; the
    same rounding as ``reduce_precision(4, 3)`` for scores under 240)."""
    import ml_dtypes
    return np.asarray(scores, np.float32).astype(
        ml_dtypes.float8_e4m3fn).astype(np.float32).astype(np.int32)


@dataclasses.dataclass
class Scan:
    """Per-row reductions of one reference pass over a corpus."""

    best: np.ndarray         # (R, Q) int32 best score
    loc: np.ndarray          # (R, Q) int32 first best alignment
    hot: np.ndarray          # (R, Q) bool: some alignment >= threshold


@functools.lru_cache(maxsize=None)
def _block_fn(fp8: bool):
    import jax
    import jax.numpy as jnp

    def fn(codes, rhs, thr):
        # (B, 4, F) one-hot corpus block against (Q, 4, P) accept masks.
        lhs = (codes[:, None, :] == jnp.arange(4, dtype=codes.dtype)[
            None, :, None]).astype(jnp.float32)
        s = jax.lax.conv_general_dilated(
            lhs, rhs, window_strides=(1,), padding="VALID",
            dimension_numbers=("NCH", "OIH", "NCH"),
            preferred_element_type=jnp.float32)          # (B, Q, L)
        if fp8:
            # An explicit rounding op: a round trip through a float8 type
            # may be elided by the compiler (excess precision is allowed).
            s = jax.lax.reduce_precision(s, exponent_bits=4, mantissa_bits=3)
        s = s.astype(jnp.int32)
        return (s.max(axis=2), jnp.argmax(s, axis=2).astype(jnp.int32),
                (s >= thr[None, :, None]).any(axis=2))
    return jax.jit(fn)


def scan(rows: np.ndarray, masks: np.ndarray,
         thresholds: Optional[np.ndarray] = None, *,
         precision: str = "exact", block_rows: int = BLOCK_ROWS) -> Scan:
    """Best score, first best alignment and threshold flag of every row
    for every pattern, counted on the default JAX device in row blocks.

    ``thresholds`` is (Q,) (absent: no row is hot).  Every block has the
    same shape, so one program serves a whole corpus.
    """
    import jax.numpy as jnp

    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    masks = np.atleast_2d(np.asarray(masks, np.uint8))
    Q, P = masks.shape
    # Patterns pad to a power of two (all-zero masks score 0), so a
    # sample of any size up to it reuses one program.
    q_pad = max(8, 1 << (Q - 1).bit_length())
    masks = np.concatenate([masks, np.zeros((q_pad - Q, P), np.uint8)])
    rhs = ((masks[:, None, :] >> np.arange(4, dtype=np.uint8)[None, :, None])
           & 1).astype(np.float32)                      # (Q, 4, P)
    # Integer thresholds (scores are integers); none: above any score.
    thr = np.full(q_pad, 1 << 30, np.int64)
    if thresholds is not None:
        t = np.asarray(thresholds, np.float64)
        thr[:Q] = np.where(np.isfinite(t), np.ceil(t), 1 << 30)
    fn = _block_fn(precision == "fp8")
    rhs_d, thr_d = jnp.asarray(rhs), jnp.asarray(thr.astype(np.int32))
    R = rows.shape[0]
    block = min(block_rows, -(-R // 8) * 8)
    out = []
    for b0 in range(0, R, block):
        blk = rows[b0:b0 + block]
        if blk.shape[0] < block:
            blk = np.concatenate(
                [blk, np.zeros((block - blk.shape[0], rows.shape[1]),
                               np.uint8)])
        out.append(fn(jnp.asarray(blk), rhs_d, thr_d))
    best = np.concatenate([np.asarray(b) for b, _, _ in out])[:R, :Q]
    loc = np.concatenate([np.asarray(l) for _, l, _ in out])[:R, :Q]
    hot = np.concatenate([np.asarray(h) for _, _, h in out])[:R, :Q]
    return Scan(best, loc, hot)


def topk(best: np.ndarray, k: int) -> tuple:
    """((k,) rows, (k,) scores) of one pattern's (R,) best scores:
    highest score first, ties to the lower row id."""
    best = np.asarray(best)
    k = min(int(k), best.size)
    part = np.argpartition(-best, k - 1)[:k]
    cand = np.flatnonzero(best >= best[part].min())
    order = cand[np.lexsort((cand, -best[cand]))][:k]
    return order.astype(np.int64), best[order].astype(np.int32)


def hits(rows: np.ndarray, masks: np.ndarray, threshold: float,
         hot_rows: np.ndarray, precision: str = "exact") -> np.ndarray:
    """(n, 3) int64 (row, alignment, score) of one pattern, in row then
    alignment order, over the rows ``scan`` flagged hot."""
    hot_rows = np.asarray(hot_rows, np.int64)
    if hot_rows.size == 0:
        return np.zeros((0, 3), np.int64)
    sc = scores_np(rows[hot_rows], masks)[:, :, 0]
    if precision == "fp8":
        sc = round_fp8(sc)
    r, loc = np.nonzero(sc >= threshold)
    return np.stack([hot_rows[r], loc, sc[r, loc]], 1).astype(np.int64)


def answers(rows: np.ndarray, masks: np.ndarray, specs: List[dict], *,
            precision: str = "exact") -> List[dict]:
    """Reference answers of several queries over one corpus in one scan.

    ``specs[q]`` is ``{"reduction": "topk", "k": k}`` or
    ``{"reduction": "threshold", "threshold": t}``.  Each answer holds
    ``best``/``loc`` (per row) and ``topk_rows``/``topk_scores`` or
    ``hits``.
    """
    thr = np.array([s.get("threshold", np.inf) for s in specs], np.float64)
    sc = scan(rows, masks, thr, precision=precision)
    out = []
    for q, spec in enumerate(specs):
        ans = {"best": sc.best[:, q], "loc": sc.loc[:, q]}
        if spec["reduction"] == "topk":
            ans["topk_rows"], ans["topk_scores"] = topk(sc.best[:, q],
                                                        spec["k"])
        elif spec["reduction"] == "threshold":
            ans["hits"] = hits(rows, masks[q], spec["threshold"],
                               np.flatnonzero(sc.hot[:, q]), precision)
        out.append(ans)
    return out
