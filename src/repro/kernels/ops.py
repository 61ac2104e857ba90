"""Thin compat wrappers over the match engine + bulk-bitwise kernels.

``match_scores`` is a one-shot shim over ``repro.match`` kept for callers
that match once against a throwaway fragment set (tests, examples).  All
host-side packing, padding and kernel selection lives in the engine layer
(``repro.match``: PackedCorpus / Planner / MatchEngine); long-lived
consumers hold a ``MatchEngine`` so the corpus stays device-resident
across queries instead of being repacked per call.

``popcount`` and ``bitwise`` remain direct kernel wrappers (their operands
are query data, not a resident corpus).  ``interpret`` defaults to True off
TPU (kernel bodies execute via the Pallas interpreter, which is how this
CPU container validates them); on TPU it compiles to Mosaic.
"""

from __future__ import annotations

import warnings

from typing import Literal, Optional

import jax.numpy as jnp
import numpy as np

from . import bitwise as _bitwise
from . import default_interpret
from . import popcount as _popcount


def _pad_rows(x: np.ndarray, mult: int) -> np.ndarray:
    r = (-x.shape[0]) % mult
    if r:
        x = np.concatenate([x, np.zeros((r,) + x.shape[1:], x.dtype)], 0)
    return x


def match_scores(fragments: np.ndarray, patterns,
                 method: Optional[Literal["swar", "mxu", "ref"]] = None,
                 interpret: bool | None = None, *,
                 backend: Optional[str] = None) -> np.ndarray:
    """Similarity scores for all alignments (Algorithm 1 fast path).

    fragments: (R, F) uint8 codes.  patterns: (P,) shared, (R, P) per-row,
    or (Q, P) batched (-> (R, L, Q)) uint8 codes -- or a
    ``repro.match.MatchQuery`` (whose reduction is forced to "full"),
    which is how wildcard / IUPAC predicates reach this shim.  Returns
    (R, L) int32 or (R, L, Q) int32, L = F - P + 1.

    ``backend=None`` lets the planner pick the kernel from the workload
    shape; pass an explicit name to override (``method=`` is the
    deprecated spelling).  One-shot path: packs the fragments for this
    call only -- hold a ``repro.match.MatchEngine`` to amortize packing
    across queries.
    """
    from repro.match import MatchEngine

    if method is not None:
        warnings.warn("ops.match_scores(method=...) is deprecated; pass "
                      "backend=... or compile a MatchQuery",
                      DeprecationWarning, stacklevel=2)
        if backend is None:
            backend = method

    eng = MatchEngine(np.asarray(fragments, np.uint8), interpret=interpret)
    # The streaming executor materializes on host; hand that array back
    # rather than re-uploading (every caller consumes it as numpy).
    kw = {} if backend is None else {"backend": backend}
    return eng.scores(patterns if hasattr(patterns, "masks_b")
                      else np.asarray(patterns, np.uint8), **kw)


def popcount(words: np.ndarray, interpret: bool | None = None) -> jnp.ndarray:
    """(N, W) uint32 -> (N,) int32."""
    if interpret is None:
        interpret = default_interpret()
    words = np.asarray(words, np.uint32)
    N = words.shape[0]
    padded = _pad_rows(words, _popcount.N_TILE)
    out = _popcount.popcount(jnp.asarray(padded), interpret=interpret)
    return out[:N, 0]


def bitwise(op: str, a: np.ndarray, b: np.ndarray | None = None,
            interpret: bool | None = None) -> jnp.ndarray:
    """Bulk bitwise op over (N, W) uint32 operands."""
    if interpret is None:
        interpret = default_interpret()
    a = np.asarray(a, np.uint32)
    N = a.shape[0]
    ap = _pad_rows(a, _bitwise.N_TILE)
    bp = ap if b is None else _pad_rows(np.asarray(b, np.uint32), _bitwise.N_TILE)
    out = _bitwise.bitwise(op, jnp.asarray(ap), jnp.asarray(bp),
                           interpret=interpret)
    return out[:N]
